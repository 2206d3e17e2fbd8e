"""Mean engine rounds of the solves completed in the window
(``RunResult.rounds``): what ordering and the engine cut."""
from lib.readers import solves_in_window


def read(env):
    s = solves_in_window(env)
    return sum(x["rounds"] for x in s) / len(s) if s else None
