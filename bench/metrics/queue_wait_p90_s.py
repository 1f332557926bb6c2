"""Scheduler: 90th percentile over the window's requests of the wait from
the router's receipt to the engine's admission (t2 - t1), seconds."""
from bench.e2e import due_in, percentile


def read(run):
    waits = []
    for s in due_in(run.sent, run.t_open, run.t_close):
        req = run.server.get(s.rid)
        if req is not None and req.t1 and req.t2:
            waits.append(req.t2 - req.t1)
    return percentile(waits, 90) if waits else None
