"""Kernel ``mla_paged_pallas`` (latent paged attention, prefill and decode):
the least time of the traced steps' latent attention calls (the
architecture module's ``attn_work``, lower bounds) over their device time
in the trace, percent. Nothing where the trace holds no such kernel."""
from bench.flops import least_time
from bench.metrics._work import traced_steps


def read(run):
    steps = traced_steps(run)
    device_s = (run.trace or {}).get("kernel_s", {}).get("mla_paged_pallas", 0.0)
    if not steps or not device_s or not run.peak:
        return None
    arch, w = run.arch, run.widths
    least = 0.0
    for rec, work in steps:
        for rows in (work["prefill"], [(c, 1) for c in work["decode"]]):
            if rows:
                least += w["L"] * least_time(*arch.attn_work(w, rows), run.peak)
    return 100.0 * least / device_s
