"""Parameter specification system.

A model's parameters are a nested dict of ``ParamSpec`` leaves — the single
source of truth for shapes, **logical sharding axes**, init, and dtype. From
the spec tree we derive: random init (tests/examples), ShapeDtypeStruct trees
(dry-run, no allocation), PartitionSpec trees (via distributed.sharding
rules), and analytic parameter counts (roofline 6·N·D).

Repeated layers are stacked on a leading "layers" dim (lax.scan over layer
groups), so a group with pattern "LG" x 23 contributes two layer param dicts,
each leaf shaped (23, ...).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "lecun"          # lecun | normal02 | zeros | ones | custom inits below
    tag: str = ""                # "routed_expert" marks MoE routed weights (active-count)

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _attn_specs(cfg: ModelConfig, R: int) -> Dict[str, Any]:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s: Dict[str, Any] = {
        "wq": ParamSpec((R, d, H, hd), ("layers", "embed", "heads", "head_dim")),
        "wk": ParamSpec((R, d, Hkv, hd), ("layers", "embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((R, d, Hkv, hd), ("layers", "embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((R, H, hd, d), ("layers", "heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((R, H, hd), ("layers", "heads", "head_dim"), "zeros")
        s["bk"] = ParamSpec((R, Hkv, hd), ("layers", "kv_heads", "head_dim"), "zeros")
        s["bv"] = ParamSpec((R, Hkv, hd), ("layers", "kv_heads", "head_dim"), "zeros")
    return s


def _mla_specs(cfg: ModelConfig, R: int) -> Dict[str, Any]:
    """Latent attention without a query LoRA (``models/mla.py``): the query,
    the joint latent + rope-key down-projection, the latent's norm, the
    key and value up-projections (kept apart, so that the absorbed form
    needs nothing prepared at load) and the output."""
    d, H, m = cfg.d_model, cfg.n_heads, cfg.mla
    r = m.kv_lora_rank
    return {
        "wq": ParamSpec((R, d, H, m.q_head_dim), ("layers", "embed", "heads", "head_dim")),
        "wkv_a": ParamSpec((R, d, m.cache_dim), ("layers", "embed", None)),
        "kv_norm": {"w": ParamSpec((R, r), ("layers", None), "ones")},
        "wk_b": ParamSpec((R, r, H, m.qk_nope_head_dim), ("layers", "latent", "heads", "head_dim")),
        "wv_b": ParamSpec((R, r, H, m.v_head_dim), ("layers", "latent", "heads", "head_dim")),
        "wo": ParamSpec((R, H, m.v_head_dim, d), ("layers", "heads", "head_dim", "embed")),
    }


def _dense_mlp_specs(cfg: ModelConfig, R: int, d_ff: int) -> Dict[str, Any]:
    d = cfg.d_model
    return {
        "wi": ParamSpec((R, d, d_ff), ("layers", "embed", "mlp")),
        "wg": ParamSpec((R, d, d_ff), ("layers", "embed", "mlp")),
        "wo": ParamSpec((R, d_ff, d), ("layers", "mlp", "embed")),
    }


def _moe_specs(cfg: ModelConfig, R: int) -> Dict[str, Any]:
    d, m = cfg.d_model, cfg.moe
    fe = m.d_expert or cfg.d_ff
    s: Dict[str, Any] = {
        "router": ParamSpec((R, d, m.num_experts), ("layers", "embed", None), "normal02"),
        "wg": ParamSpec((R, m.num_experts, d, fe), ("layers", "experts", "embed", "expert_mlp"), tag="routed_expert"),
        "wu": ParamSpec((R, m.num_experts, d, fe), ("layers", "experts", "embed", "expert_mlp"), tag="routed_expert"),
        "wd": ParamSpec((R, m.num_experts, fe, d), ("layers", "experts", "expert_mlp", "embed"), tag="routed_expert"),
    }
    if m.num_shared_experts > 0:
        fs = fe * m.num_shared_experts
        s["shared"] = _dense_mlp_specs(cfg, R, fs)
    return s


def _ssm_specs(cfg: ModelConfig, R: int) -> Dict[str, Any]:
    d, ssm = cfg.d_model, cfg.ssm
    d_in = cfg.d_inner
    H = cfg.ssm_heads
    G, N = ssm.n_groups, ssm.d_state
    d_proj = 2 * d_in + 2 * G * N + H     # z, x, B, C, dt
    conv_dim = d_in + 2 * G * N           # x, B, C go through the causal conv
    return {
        "in_proj": ParamSpec((R, d, d_proj), ("layers", "embed", "ssm_proj")),
        "conv_w": ParamSpec((R, conv_dim, ssm.d_conv), ("layers", "conv_dim", None)),
        "conv_b": ParamSpec((R, conv_dim), ("layers", "conv_dim"), "zeros"),
        "A_log": ParamSpec((R, H), ("layers", "ssm_heads"), "a_log"),
        "D": ParamSpec((R, H), ("layers", "ssm_heads"), "ones"),
        "dt_bias": ParamSpec((R, H), ("layers", "ssm_heads"), "dt_bias"),
        "norm": ParamSpec((R, d_in), ("layers", "ssm_inner"), "ones"),
        "out_proj": ParamSpec((R, d_in, d), ("layers", "ssm_inner", "embed")),
    }


def _layer_specs(cfg: ModelConfig, kind: str, is_moe: bool, R: int, *, cross: bool = False,
                 dense_first: bool = False) -> Dict[str, Any]:
    d = cfg.d_model
    spec: Dict[str, Any] = {"ln1": ParamSpec((R, d), ("layers", "embed"), "ones")}
    if kind == "M":
        spec["ssm"] = _ssm_specs(cfg, R)
    elif cfg.mla is not None:
        spec["attn"] = _mla_specs(cfg, R)
    else:
        spec["attn"] = _attn_specs(cfg, R)
    if cross:
        spec["ln_x"] = ParamSpec((R, d), ("layers", "embed"), "ones")
        spec["cross"] = _attn_specs(cfg, R)
    if is_moe and cfg.moe is not None:
        spec["ln2"] = ParamSpec((R, d), ("layers", "embed"), "ones")
        spec["moe"] = _moe_specs(cfg, R)
    elif cfg.d_ff > 0 or dense_first:
        d_ff = cfg.dense_d_ff if (dense_first and cfg.dense_d_ff) else cfg.d_ff
        spec["ln2"] = ParamSpec((R, d), ("layers", "embed"), "ones")
        spec["mlp"] = _dense_mlp_specs(cfg, R, d_ff)
    return spec


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "embed": {"w": ParamSpec((cfg.vocab, d), ("vocab", "embed"), "normal02")},
        "final_norm": {"w": ParamSpec((d,), ("embed",), "ones")},
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"w": ParamSpec((d, cfg.vocab), ("embed", "vocab"))}

    groups = []
    cross = cfg.family == "encdec"
    for gi, g in enumerate(cfg.layer_groups):
        layers = []
        for pos, kind in enumerate(g.pattern):
            is_moe = bool(g.moe_mask and g.moe_mask[pos % len(g.moe_mask)] == "1")
            dense_first = (gi == 0 and pos == 0 and cfg.dense_d_ff > 0 and not is_moe)
            layers.append(_layer_specs(cfg, kind, is_moe, g.repeats, cross=cross,
                                       dense_first=dense_first))
        groups.append({"layers": layers})
    specs["groups"] = groups

    if cfg.encoder is not None:
        enc_layers = [_layer_specs(cfg, "A", False, cfg.encoder.n_layers)]
        specs["encoder"] = {
            "groups": [{"layers": enc_layers}],
            "final_norm": {"w": ParamSpec((d,), ("embed",), "ones")},
        }
    if cfg.vision is not None:
        specs["vision_proj"] = {
            "w": ParamSpec((cfg.vision.d_patch, d), (None, "embed")),
            "b": ParamSpec((d,), ("embed",), "zeros"),
        }
    return specs


# --------------------------------------------------------------------------
def _init_leaf(spec: ParamSpec, key, dtype):
    shape = spec.shape
    if spec.init == "zeros":
        return jnp.zeros(shape, dtype)
    if spec.init == "ones":
        return jnp.ones(shape, dtype)
    if spec.init == "a_log":
        # mamba2: A ~ uniform[1, 16], stored as log
        u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if spec.init == "dt_bias":
        # inverse softplus of dt ~ uniform[1e-3, 1e-1]
        dt = jax.random.uniform(key, shape, jnp.float32, 1e-3, 1e-1)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if spec.init == "normal02":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    # lecun: fan_in = product of all non-output dims after the stacking dim.
    # For (R, in, out...) matrices we take dim 1 (or dim 0 for 2D).
    fan_in = shape[-2] if len(shape) >= 2 else shape[0]
    if len(shape) == 4:            # (R, in, h, hd) or (R, E, in, out)
        fan_in = shape[1] if spec.logical[1] in ("embed", "latent") else shape[2]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_params(cfg: ModelConfig, rng, dtype=jnp.float32):
    specs = param_specs(cfg)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(rng, len(leaves))
    vals = [_init_leaf(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(cfg: ModelConfig, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree (dry-run: no allocation)."""
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    specs = param_specs(cfg)
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    total = 0
    for s in leaves:
        n = int(np.prod(s.shape))
        if active_only and s.tag == "routed_expert":
            n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n
    return total
