"""Router: requests due in the window that the busiest replica took, over
the mean per replica (1 is even). From the gateway's records."""
from collections import Counter

from bench.e2e import due_in


def read(run):
    if run.chips < 2:
        return None
    due = due_in(run.sent, run.t_open, run.t_close)
    per = Counter(getattr(run.server.get(s.rid), "replica_id", None) for s in due)
    per.pop(None, None)
    if not per:
        return None
    counts = [per.get(r, 0) for r in run.stats_open]
    return max(counts) / (sum(counts) / len(counts))
