"""Decoders of latent-attention layers (MLA, no query LoRA) with sparse
experts (DeepSeek-V2-Lite): the mapping of their published keys onto the
program's ``ModelConfig``, and the operations and bytes of the work a step
did.

Counts (``bench.arch`` says why each is a lower bound): a latent layer
caches ``r + rope`` values a token, shared by every head, and reads each
row's resident latent rows once. Its attention FLOPs are the lesser of the
absorbed form (every head scores ``r + rope`` columns and sums ``r``) and
the expanded form (every head scores ``nope + rope`` and sums ``dv``, after
up-projecting each resident latent row to every head's key and value).
The experts are ``moe_decoder``'s.
"""
from __future__ import annotations

from typing import Sequence, Tuple

from bench.arch.moe_decoder import gmm_work  # noqa: F401  (the experts' counts)

# fan-in of the latent up-projections (R, r, H, k): the latent, not the heads
FAN_IN = {"wk_b": lambda shape: shape[-3], "wv_b": lambda shape: shape[-3]}


def widths(cfg: dict) -> dict:
    dense = cfg.get("first_k_dense_replace", 0)
    return dict(
        d=cfg["hidden_size"], H=cfg["num_attention_heads"], L=cfg["num_hidden_layers"],
        V=cfg["vocab_size"], E=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        f=cfg["moe_intermediate_size"], shared=cfg.get("n_shared_experts") or 0,
        dense_layers=dense, f_dense=cfg["intermediate_size"] if dense else 0,
        r=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        norm_topk=bool(cfg["norm_topk_prob"]))


def check_supported(cfg: dict) -> None:
    """Refuse what the program's latent MoE decoder does not compute."""
    bad = []
    if cfg.get("q_lora_rank"):
        bad.append(f"q_lora_rank {cfg['q_lora_rank']}")
    scaling = cfg.get("rope_scaling") or {}
    if scaling and scaling.get("type") != "yarn":
        bad.append(f"rope_scaling {scaling.get('type')}")
    if cfg.get("scoring_func", "softmax") != "softmax":
        bad.append(f"scoring_func {cfg['scoring_func']}")
    if cfg.get("topk_method", "greedy") != "greedy":
        bad.append(f"topk_method {cfg['topk_method']}")
    if cfg.get("routed_scaling_factor", 1) != 1:
        bad.append(f"routed_scaling_factor {cfg['routed_scaling_factor']}")
    if cfg.get("hidden_act", "silu") != "silu":
        bad.append(f"hidden_act {cfg['hidden_act']}")
    if cfg.get("attention_bias"):
        bad.append("attention_bias")
    if cfg.get("tie_word_embeddings"):
        bad.append("tie_word_embeddings")
    if cfg.get("moe_layer_freq", 1) != 1:
        bad.append("moe_layer_freq != 1")
    if bad:
        raise ValueError(f"{cfg['name']}: the program cannot run {', '.join(bad)}")


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.configs.base import LayerGroup, MLAConfig, ModelConfig, MoEConfig, YarnConfig

    check_supported(cfg)
    w = widths(cfg)
    groups = []
    if w["dense_layers"]:
        groups.append(LayerGroup("A", w["dense_layers"], moe_mask="0"))
    groups.append(LayerGroup("A", w["L"] - w["dense_layers"], moe_mask="1"))
    scaling = dict(cfg.get("rope_scaling") or {})
    scaling.pop("type", None)
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=w["L"], d_model=w["d"],
        n_heads=w["H"], n_kv_heads=cfg["num_key_value_heads"], head_dim=w["dv"],
        d_ff=w["f"], vocab=w["V"], rope_theta=w["theta"], rms_eps=w["eps"],
        dense_d_ff=w["f_dense"],
        moe=MoEConfig(num_experts=w["E"], top_k=w["k"], num_shared_experts=w["shared"],
                      d_expert=w["f"], norm_topk=w["norm_topk"]),
        mla=MLAConfig(kv_lora_rank=w["r"], qk_nope_head_dim=w["nope"],
                      qk_rope_head_dim=w["rope"], v_head_dim=w["dv"]),
        rope_yarn=YarnConfig(**scaling) if scaling else None,
        layer_groups=tuple(groups), source=cfg["source"])


def layer_kinds(w: dict) -> list:
    return ["dense"] * w["dense_layers"] + ["moe"] * (w["L"] - w["dense_layers"])


def matmul_params(w: dict, kind: str) -> int:
    """Parameters one token multiplies through in one layer whichever form
    attention takes (the query, the latent down-projection and the output;
    the up-projections are in ``attn_work``), active experts only, the
    router included."""
    d, H = w["d"], w["H"]
    attn = d * H * (w["nope"] + w["rope"]) + d * (w["r"] + w["rope"]) + H * w["dv"] * d
    if kind == "dense":
        return attn + 3 * d * w["f_dense"]
    return attn + d * w["E"] + 3 * d * w["f"] * (w["k"] + w["shared"])


def attn_work(w: dict, rows: Sequence[Tuple[int, int]], nbytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one latent attention call in one layer: ``rows`` of
    (start, n), a chunk of n query tokens after ``start`` resident ones.
    Per row the FLOPs are the lesser of the absorbed and the expanded form;
    the bytes are the row's resident latent rows read once, with the
    narrower (expanded) query in and output out."""
    H, r, nope, rope, dv = w["H"], w["r"], w["nope"], w["rope"], w["dv"]
    flops, values = 0, 0
    for s, n in rows:
        pairs = n * s + n * (n + 1) // 2              # unmasked query-key pairs
        absorbed = 2 * H * (r + rope + r) * pairs
        expanded = 2 * H * (nope + rope + dv) * pairs + 2 * r * H * (nope + dv) * (s + n)
        flops += min(absorbed, expanded)
        values += (s + n) * (r + rope) + n * H * (nope + rope) + n * H * dv
    return float(flops), float(values * nbytes)


def model_flops(w: dict, tokens: int, rows: int, attn_rows: Sequence[Tuple[int, int]]) -> float:
    """Model FLOPs of one engine step: ``tokens`` fed, ``rows`` scored by
    the head, and the attention of ``attn_rows`` in every layer."""
    per_token = sum(2 * matmul_params(w, kind) for kind in layer_kinds(w))
    attn = attn_work(w, attn_rows)[0] * w["L"]
    return float(per_token * tokens + attn + 2 * w["d"] * w["V"] * rows)
