"""Shared model-runtime context and small layer primitives."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import resolve


@dataclass(frozen=True)
class RunCtx:
    """Execution knobs threaded through every layer. What a sublayer runs is
    set by its arguments, not here: the full sequence with no cache (train,
    ``LM.forward``, the encoder), or a chunk over the paged pool when the
    chunk pack (``LM.decode_chunk``) is given.

    attn_backend: "auto" | "pallas" | "xla" for attention and the MoE gmm
        (xla = the jnp references, which the dry-run lowers; pallas = the
        TPU kernels). "auto" is resolved once, at construction, from the
        platform: compiled Pallas on a TPU, xla elsewhere.
    interpret: run Pallas kernels in the interpreter (CPU tests only)
    moe_strategy: "dropless" (exact, serving engine) | "capacity" (local
        capacity buffers) | "tp_shardmap" | "ep_shardmap" (explicit collectives)
    """
    mesh: Any = None
    attn_backend: str = "auto"
    moe_strategy: str = "capacity"
    remat: bool = False
    block_q: int = 512
    block_kv: int = 1024
    ep_axis: str = "data"
    tp_axis: str = "model"
    interpret: bool = False
    quant: str = "none"         # none | a2a_int8 (int8 all-to-all in ep_shardmap)
    # Cost-model lowering knobs (launch/dryrun): XLA's cost_analysis counts
    # loop bodies ONCE, so the roofline cost lowering unrolls layers and
    # attention tiles (small repeat counts; affine extrapolation).
    scan_layers: bool = True
    attn_unroll: bool = False

    def __post_init__(self):
        backend, _ = resolve(self.attn_backend, self.interpret)
        object.__setattr__(self, "attn_backend", backend)


def rmsnorm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (w.astype(jnp.float32))).astype(dt)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(yarn, dim: int, theta: float) -> Tuple[int, int]:
    """(low, high): the frequency pairs below ``low`` rotate more than
    ``beta_fast`` times over the original context and keep their frequency;
    those above ``high`` rotate less than ``beta_slow`` times and are
    interpolated by ``factor``; a linear ramp blends the pairs between."""
    def pair(rotations):
        return dim * math.log(yarn.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(pair(yarn.beta_fast)), 0),
            min(math.ceil(pair(yarn.beta_slow)), dim - 1))


def yarn_freqs(yarn, dim: int, theta: float) -> np.ndarray:
    """The ``dim // 2`` rope frequencies under YaRN (float32)."""
    half = dim // 2
    base = theta ** (-np.arange(half, dtype=np.float64) / half)
    low, high = yarn_correction_range(yarn, dim, theta)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (base * (1.0 - ramp) + base / yarn.factor * ramp).astype(np.float32)


def rope(x, positions, theta: float, yarn: Optional[Any] = None):
    """x: (B, S, H, hd); positions: (S,) or (B, S) absolute token positions.
    ``yarn`` (a ``YarnConfig``) scales the frequencies and cos/sin as YaRN does."""
    B, S, H, hd = x.shape
    half = hd // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn_freqs(yarn, hd, theta))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions.astype(jnp.float32)[:, :, None] * freqs  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def act_fn(name: str):
    return jax.nn.gelu if name == "gelu" else jax.nn.silu


def dense_mlp(p, x, act_name: str):
    act = act_fn(act_name)
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    return jnp.einsum("bsf,fd->bsd", act(g) * h, p["wo"])


def shard_act(x, logical_axes):
    """Apply a with_sharding_constraint from the active logical-axis rules
    (no-op when no rules are installed — CPU unit tests)."""
    from repro.distributed.sharding import constrain
    return constrain(x, logical_axes)
