"""Percent of its roofline the sweep program reached while serving, the
useful columns being the occupied slots of each batch."""
from lib.readers import serve_roofline


def read(env):
    return serve_roofline(env)
