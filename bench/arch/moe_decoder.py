"""Decoders of plain attention layers with sparse experts (Mixtral,
DeepSeek-MoE): the mapping of their published keys onto the program's
``ModelConfig``, and the operations and bytes of the work a step did.

Counts (``bench.arch`` says why each is a lower bound): attention reads
K and V of ``Hkv`` heads; a grouped-matmul call of T live tokens routes
each to ``k`` distinct experts, so it reads at least ``k`` experts' weights
(all ``E`` cannot be assumed). ``nbytes`` is the served dtype's size.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple


def widths(cfg: dict) -> dict:
    """Published MoE-decoder widths under one set of names (Mixtral and
    DeepSeek-MoE name them differently)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    E = cfg.get("num_local_experts") or cfg.get("n_routed_experts")
    dense = cfg.get("first_k_dense_replace", 0)
    return dict(
        d=d, H=H, Hkv=cfg["num_key_value_heads"], hd=d // H,
        L=cfg["num_hidden_layers"], V=cfg["vocab_size"], E=E,
        k=cfg["num_experts_per_tok"],
        f=cfg.get("moe_intermediate_size") or cfg["intermediate_size"],
        shared=cfg.get("n_shared_experts") or 0,
        dense_layers=dense, f_dense=cfg["intermediate_size"] if dense else 0,
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def check_supported(cfg: dict) -> None:
    """Refuse what the program's MoE decoder does not compute."""
    bad = []
    if cfg.get("hidden_act", "silu") != "silu":
        bad.append(f"hidden_act {cfg['hidden_act']}")
    if cfg.get("attention_bias"):
        bad.append("attention_bias")
    if cfg.get("tie_word_embeddings"):
        bad.append("tie_word_embeddings")
    if cfg.get("sliding_window"):
        bad.append("sliding_window")
    if cfg.get("scoring_func", "softmax") != "softmax":
        bad.append(f"scoring_func {cfg['scoring_func']}")
    # Mixtral's softmax over the top-k logits equals a renormalized top-k of
    # the full softmax, which is what the program computes
    if cfg.get("norm_topk_prob") is False:
        bad.append("norm_topk_prob false (the program always renormalizes)")
    if cfg.get("moe_layer_freq", 1) != 1:
        bad.append("moe_layer_freq != 1")
    if bad:
        raise ValueError(f"{cfg['name']}: the program cannot run {', '.join(bad)}")


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.configs.base import LayerGroup, ModelConfig, MoEConfig

    check_supported(cfg)
    w = widths(cfg)
    groups = []
    if w["dense_layers"]:
        groups.append(LayerGroup("A", w["dense_layers"], moe_mask="0"))
    groups.append(LayerGroup("A", w["L"] - w["dense_layers"], moe_mask="1"))
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=w["L"], d_model=w["d"],
        n_heads=w["H"], n_kv_heads=w["Hkv"], head_dim=w["hd"], d_ff=w["f"],
        vocab=w["V"], rope_theta=w["theta"], rms_eps=w["eps"],
        dense_d_ff=w["f_dense"],
        moe=MoEConfig(num_experts=w["E"], top_k=w["k"],
                      num_shared_experts=w["shared"], d_expert=w["f"]),
        layer_groups=tuple(groups), source=cfg["source"])


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


def gmm_work(w: dict, tokens: int, nbytes: int = 2,
             reached: Optional[int] = None) -> Tuple[float, float]:
    """(flops, bytes) of the three grouped matmuls of one MoE layer over
    ``tokens`` live tokens (gate and up d->f, down f->d), streaming the
    weights of ``reached`` experts (by default the ``k`` every call reaches)."""
    if tokens <= 0:
        return 0.0, 0.0
    d, f, k = w["d"], w["f"], w["k"]
    rows = tokens * k
    flops = 3 * 2 * rows * d * f
    weights = 3 * (k if reached is None else reached) * d * f
    acts = 2 * rows * (d + f) + rows * (f + d)              # gate, up: d->f; down: f->d
    return float(flops), float((weights + acts) * nbytes)


def model_flops(w: dict, tokens: int, rows: int, attn_rows: Sequence[Tuple[int, int]]) -> float:
    """Model FLOPs of one engine step: ``tokens`` fed, ``rows`` scored by
    the head, and the attention of ``attn_rows`` in every layer."""
    per_token = sum(2 * matmul_params(w, kind) for kind in layer_kinds(w))
    attn = attn_work(w, attn_rows)[0] * w["L"]
    return float(per_token * tokens + attn + 2 * w["d"] * w["V"] * rows)
