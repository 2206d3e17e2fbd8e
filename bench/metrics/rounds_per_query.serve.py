"""Mean engine rounds of the window's queries that ran (``Ticket.rounds``)."""
from lib.readers import ran


def read(env):
    t = ran(env)
    return sum(x["rounds"] for x in t) / len(t) if t else None
