"""Mean host seconds of the program's ``pack`` span per solve completed in
the window: packing the graph into blocks or tiles and placing it."""
from lib.readers import solves_in_window


def read(env):
    s = [x["pack_s"] for x in solves_in_window(env) if x["pack_s"] is not None]
    return sum(s) / len(s) if s else None
