"""Kernel ``chunked_prefill_pallas`` (paged attention, prefill and decode):
the least time of the traced steps' attention calls (``bench.flops``, lower
bounds) over their device time in the trace, percent."""
from bench.flops import attn_work, least_time
from bench.metrics._work import traced_steps


def read(run):
    steps = traced_steps(run)
    device_s = (run.trace or {}).get("kernel_s", {}).get("chunked_prefill_pallas", 0.0)
    if not steps or not device_s or not run.peak:
        return None
    least = 0.0
    for rec, work in steps:
        for rows in (work["prefill"], [(c, 1) for c in work["decode"]]):
            if rows:
                least += run.widths["L"] * least_time(*attn_work(run.widths, rows), run.peak)
    return 100.0 * least / device_s
