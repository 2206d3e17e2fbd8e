"""Percent of the traced serving window with no operation on the device."""
from lib.readers import idle_share


def read(env):
    return idle_share(env)
