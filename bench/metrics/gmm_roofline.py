"""Kernel ``gmm_pallas``: the least time of the traced steps' grouped
matmuls (the larger of operations over peak FLOP/s and bytes over HBM
bandwidth, each a lower bound: the architecture module's ``gmm_work``) over
their device time in the trace, percent."""
from bench.flops import least_time
from bench.metrics._work import traced_steps


def read(run):
    steps = traced_steps(run)
    device_s = (run.trace or {}).get("kernel_s", {}).get("gmm_pallas", 0.0)
    if not steps or not device_s or not run.peak:
        return None
    arch, w = run.arch, run.widths
    n_moe = arch.layer_kinds(w).count("moe")
    least = 0.0
    for rec, work in steps:
        calls = [sum(n for _, n in work["prefill"]), len(work["decode"])]
        least += n_moe * sum(least_time(*arch.gmm_work(w, t), run.peak) for t in calls if t)
    return 100.0 * least / device_s
