"""Pallas TPU grouped matmul (megablox-style 'gmm') for dropless MoE.

Rows are sorted by expert and padded so every expert's rows occupy whole
``block_m`` tiles (the ops wrapper builds that layout with one scatter).
``tile_expert`` — the m-tile -> expert map — is a scalar-prefetch operand, so
``BlockSpec.index_map`` streams exactly one expert's weight tile per grid
step. Grid = (m_tiles, n_tiles); K is kept whole in VMEM. The tiles enter
the MXU in their own dtype and accumulate in f32: at Mixtral's down
projection (K = 14336, bf16, 128x128 tiles) the four double-buffered input
tiles take 14.7 MB, inside v5e's default 16 MiB scoped VMEM; f32 copies of
them would not fit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(te_ref, x_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),   # (bm, K) x (K, bn)
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def gmm_pallas(
    x_padded,      # (Mp, K) tile-aligned rows, sorted by expert
    w,             # (E, K, N)
    tile_expert,   # (Mp // block_m,) int32
    *,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
):
    Mp, K = x_padded.shape
    E, _, N = w.shape
    assert Mp % block_m == 0 and N % block_n == 0
    grid = (Mp // block_m, N // block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i, j, te: (i, 0)),
            pl.BlockSpec((1, K, block_n), lambda i, j, te: (te[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, te: (i, j)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, N), x_padded.dtype),
        interpret=interpret,
    )(tile_expert, x_padded, w)
