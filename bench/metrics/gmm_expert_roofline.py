"""Kernel ``gmm_pallas`` against the experts its calls reached: as
``gmm_roofline``, the least time of the traced steps' grouped matmuls over
their device time in the trace, percent; but each call streams, in each MoE
layer, the weights of every expert that one of its live tokens reached (the
program's counter, ``StepRecord.prefill_experts`` / ``decode_experts``), not
``k`` of them, and its rows are those the call ran (``prefill_work``, the
decode rows and their drafts)."""
from bench.flops import least_time
from bench.metrics._records import traced_records


def call_least_time(arch, w: dict, tokens: int, reached, peak: dict) -> float:
    """Least time of one call's grouped matmuls: ``reached`` experts per
    MoE layer, ``tokens`` live tokens."""
    return sum(least_time(*arch.gmm_work(w, tokens, reached=e), peak) for e in reached)


def read(run):
    device_s = (run.trace or {}).get("kernel_s", {}).get("gmm_pallas", 0.0)
    records = traced_records(run, "decode_experts")
    if not records or not device_s or not run.peak:
        return None
    least = 0.0
    for rec in records:
        calls = ((sum(n for _, n in rec.prefill_work), rec.prefill_experts),
                 (rec.decode_rows + rec.drafted_tokens, rec.decode_experts))
        least += sum(call_least_time(run.arch, run.widths, t, reached, run.peak)
                     for t, reached in calls if t)
    return 100.0 * least / device_s
