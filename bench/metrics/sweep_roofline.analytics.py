"""Percent of its roofline the sweep program reached in the analytics
window: least time of the sweeps' work over the program's device time."""
from lib.readers import analytics_roofline


def read(env):
    return analytics_roofline(env)
