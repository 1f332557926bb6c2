"""Scheduler and engine, as the client sees them: 90th percentile over the
window's requests of due -> first token at the client, seconds (what
``bench.e2e`` reads as ``ttft_p90_s``). A request whose first token never
came counts as the longest wait the run gives it."""
from bench.e2e import percentile, ttfts
from bench.run import MAX_WAIT_S


def read(run):
    t = ttfts(run.sent, run.t_open, run.t_close, run.t_close + MAX_WAIT_S - run.t_open)
    return percentile(t, 90) if t else None
