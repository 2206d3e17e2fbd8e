"""Percent of the serving window's slot-rounds that held a query
(``ServerStats`` round_slots over rounds x slots)."""
from lib.readers import batch_occupancy


def read(env):
    return batch_occupancy(env)
