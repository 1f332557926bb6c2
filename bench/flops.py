"""Operations and bytes of the work the served path did, from the
configuration's widths and the lengths each call served.

Every count is a lower bound of what the step had to do, so that no share
of a peak can pass 100% on the benchmark's account:

* only live tokens count; padding rows and padding positions of a call do
  not;
* an expert's weights count only where the call's rows must have reached
  it: a call of T live tokens routes each to ``k`` distinct experts, so it
  reads at least ``k`` experts' weights (all ``E`` cannot be assumed);
* attention reads each row's resident keys and values once and computes
  the causal scores of its chunk, nothing for masked positions;
* the LM head counts one position per row of a call (the program scores
  the last position of each row).

``w`` is ``bench.model.moe_widths(cfg)``; ``nbytes`` the served dtype's size.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def layer_kinds(w: dict) -> list:
    return ["dense"] * w["dense_layers"] + ["moe"] * (w["L"] - w["dense_layers"])


def matmul_params(w: dict, kind: str) -> int:
    """Parameters one token multiplies through in one layer (active experts
    only; the router included)."""
    d, H, Hkv, hd = w["d"], w["H"], w["Hkv"], w["hd"]
    attn = d * H * hd + 2 * d * Hkv * hd + H * hd * d
    if kind == "dense":
        return attn + 3 * d * w["f_dense"]
    return attn + d * w["E"] + 3 * d * w["f"] * (w["k"] + w["shared"])


def attn_work(w: dict, rows: Sequence[Tuple[int, int]], nbytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one attention call in one layer: ``rows`` of
    (start, n), a chunk of n query tokens after ``start`` resident ones."""
    H, Hkv, hd = w["H"], w["Hkv"], w["hd"]
    flops = sum(4 * H * hd * (n * s + n * (n + 1) // 2) for s, n in rows)
    kv = sum((s + n) * Hkv * hd * 2 for s, n in rows)        # K and V read once
    qo = sum(n * H * hd * 2 for _, n in rows)                # q in, o out
    return float(flops), float((kv + qo) * nbytes)


def gmm_work(w: dict, tokens: int, nbytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of the three grouped matmuls of one MoE layer over
    ``tokens`` live tokens (gate and up d->f, down f->d)."""
    if tokens <= 0:
        return 0.0, 0.0
    d, f, k = w["d"], w["f"], w["k"]
    rows = tokens * k
    flops = 3 * 2 * rows * d * f
    weights = 3 * k * d * f                                 # k experts reached
    acts = 2 * rows * (d + f) + rows * (f + d)              # gate, up: d->f; down: f->d
    return float(flops), float((weights + acts) * nbytes)


def model_flops(w: dict, tokens: int, rows: int, attn_rows: Sequence[Tuple[int, int]]) -> float:
    """Model FLOPs of one engine step: ``tokens`` fed, ``rows`` scored by
    the head, and the attention of ``attn_rows`` in every layer."""
    per_token = sum(2 * matmul_params(w, kind) for kind in layer_kinds(w))
    attn = attn_work(w, attn_rows)[0] * w["L"]
    return float(per_token * tokens + attn + 2 * w["d"] * w["V"] * rows)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bw"])
