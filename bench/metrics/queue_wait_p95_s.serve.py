"""95th percentile of the seconds from when a query was due to when the
server installed it in a slot (``Ticket.started_at``); cache hits wait 0."""
from lib.readers import percentile


def read(env):
    waits = [(t["started"] - t["due"]) if t["started"] is not None else 0.0
             for t in env["record"].get("tickets", [])]
    return percentile(waits, 95)
