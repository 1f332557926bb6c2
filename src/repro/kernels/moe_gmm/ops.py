"""Grouped matmul op: builds the tile-aligned layout and dispatches.

``gmm(x, w, group_sizes)`` computes ``out[m] = x[m] @ w[expert_of(m)]`` for
rows sorted by expert. The wrapper scatters rows into a tile-aligned padded
buffer (each expert starts on a ``block_m`` boundary), runs the kernel (or an
einsum-select xla fallback for CPU), and gathers the real rows back. Static
worst-case padding: Mp = M + E*block_m.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve
from repro.kernels.moe_gmm import kernel as _kernel
from repro.kernels.moe_gmm.ref import expert_of_rows, gmm_reference


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "backend", "interpret"))
def gmm(
    x,             # (M, K) rows sorted by expert
    w,             # (E, K, N)
    group_sizes,   # (E,) int32, sum == M
    *,
    block_m: int = 128,
    block_n: int = 128,
    backend: str = "auto",
    interpret: bool | None = None,
):
    M, K = x.shape
    E, _, N = w.shape
    backend, interpret = resolve(backend, interpret)
    if backend == "xla":
        return gmm_reference(x, w, group_sizes)

    bn = min(block_n, N)
    # --- tile-aligned scatter ------------------------------------------------
    padded_sizes = ((group_sizes + block_m - 1) // block_m) * block_m
    Mp = ((M + block_m - 1) // block_m + E) * block_m  # static worst case, tile-aligned
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(padded_sizes)[:-1].astype(jnp.int32)])
    eid = expert_of_rows(group_sizes, M)               # (M,)
    ends = jnp.cumsum(group_sizes)
    row_in_group = jnp.arange(M) - jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])[eid]
    dst = starts[eid] + row_in_group                   # (M,)
    x_pad = jnp.zeros((Mp, K), x.dtype).at[dst].set(x)
    # m-tile -> expert map
    tile_ends = jnp.cumsum(padded_sizes) // block_m
    tile_expert = jnp.searchsorted(tile_ends, jnp.arange(Mp // block_m), side="right")
    tile_expert = jnp.minimum(tile_expert, E - 1).astype(jnp.int32)

    out_pad = _kernel.gmm_pallas(
        x_pad, w, tile_expert, block_m=block_m, block_n=bn, interpret=interpret
    )
    return out_pad[dst]
