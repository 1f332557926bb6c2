"""The least time a chip could take for some work: the larger of its
operations over the peak FLOP/s and its bytes over the HBM bandwidth.
The operations and bytes come from the configuration's architecture
module (``bench.arch``), each a lower bound."""
from __future__ import annotations


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bw"])
