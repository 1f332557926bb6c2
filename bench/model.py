"""A configuration's engine settings and its weights, for any
architecture (what belongs to one family is in ``bench.arch``).

``make_weights`` makes every leaf of the program's parameter tree from the
seed, on one device, in one jitted call, in the dtype served. The weights
are the benchmark's own: the plain reference reads the same arrays and
nothing else the program made.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def engine_kwargs(cfg: dict) -> dict:
    kw = dict(cfg["engine"])
    kw["cache_dtype"] = DTYPES[kw["cache_dtype"]]
    return kw


def weight_dtype(cfg: dict):
    return DTYPES[cfg["torch_dtype"]]


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _leaf_std(path: tuple, shape: tuple, overrides: Dict[str, Callable]) -> float:
    """N(0, 1/fan_in) for a matrix, by the axis it contracts over in the
    program's layout (stacked layers lead; experts follow); ``overrides``
    gives the fan-in of a parameter by its name, from its shape."""
    name = path[-1]
    if name == "w" and path[0] == "embed":
        return 0.02
    if name in overrides:
        return 1.0 / math.sqrt(overrides[name](shape))
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


def make_weights(model, seed: int, dtype, device,
                 fan_in: Optional[Dict[str, Callable]] = None):
    """Every parameter leaf of ``model`` from ``seed``, made on ``device``
    in one jitted call. Norm weights are 1; ``fan_in`` is the architecture
    module's ``FAN_IN``, where it has one."""
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
                std = _leaf_std(names, s.shape, fan_in or {})
                out.append((std * jax.random.normal(k, s.shape, jnp.float32)).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    from jax.sharding import SingleDeviceSharding
    return jax.jit(gen, out_shardings=SingleDeviceSharding(device))(seed_key(seed))


def param_bytes(params) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(params)))
