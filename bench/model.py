"""A configuration file as the program runs it, and its weights.

``program_config`` maps the published keys of a ``configs/<name>.json`` onto
the program's ``ModelConfig``; a key the program cannot honour is refused,
never dropped. ``make_weights`` makes every leaf of the program's parameter
tree from the seed, on one device, in one jitted call, in the dtype served.
The weights are the benchmark's own: the plain reference reads the same
arrays and nothing else the program made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def moe_widths(cfg: dict) -> dict:
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
    w = moe_widths(cfg)
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


def engine_kwargs(cfg: dict) -> dict:
    kw = dict(cfg["engine"])
    kw["cache_dtype"] = DTYPES[kw["cache_dtype"]]
    return kw


def weight_dtype(cfg: dict):
    return DTYPES[cfg["torch_dtype"]]


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _leaf_std(path: tuple, shape: tuple) -> float:
    """N(0, 1/fan_in) for a matrix, by the axis it contracts over in the
    program's layout (stacked layers lead; experts follow)."""
    name = path[-1]
    if name == "w" and path[0] == "embed":
        return 0.02
    if name in ("wq", "wk", "wv"):              # (R, d, heads, hd)
        fan_in = shape[-3]
    elif name == "wo" and "attn" in path:        # (R, heads, hd, d)
        fan_in = shape[-3] * shape[-2]
    else:                                        # (..., in, out)
        fan_in = shape[-2]
    return 1.0 / math.sqrt(fan_in)


def _path_names(path) -> tuple:
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return tuple(out)


def make_weights(model, seed: int, dtype, device):
    """Every parameter leaf of ``model`` from ``seed``, made on ``device``
    in one jitted call. Norm weights are 1."""
    shapes = jax.eval_shape(lambda k: model.init_params(k, dtype), jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (path, s), k in zip(leaves, keys):
            names = _path_names(path)
            if len(s.shape) <= 2 and names[-1] in ("ln1", "ln2", "w") and \
                    names[0] != "embed" and names[0] != "lm_head":
                out.append(jnp.ones(s.shape, s.dtype))          # norm weights
            else:
                std = _leaf_std(names, s.shape)
                out.append((std * jax.random.normal(k, s.shape, jnp.float32)).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    from jax.sharding import SingleDeviceSharding
    return jax.jit(gen, out_shardings=SingleDeviceSharding(device))(seed_key(seed))


def param_bytes(params) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(params)))
