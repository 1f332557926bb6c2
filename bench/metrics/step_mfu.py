"""Model step: model FLOPs of the tokens the traced steps fed (active
experts only, attention over each row's real context, the head once per
row) over the slice's length, the chips and the chip's bf16 peak, percent."""
from bench.metrics._work import traced_steps


def read(run):
    tr = run.trace
    steps = traced_steps(run)
    if not tr or not steps or not run.peak:
        return None
    flops = sum(run.arch.model_flops(run.widths, rec.prefill_tokens + rec.decode_tokens,
                                     rec.prefill_rows + rec.decode_rows,
                                     work["prefill"] + [(c, 1) for c in work["decode"]])
                for rec, work in steps)
    return 100.0 * flops / tr["window_s"] / run.chips / run.peak["flops_bf16"]
