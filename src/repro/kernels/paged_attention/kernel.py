"""Pallas TPU paged-attention kernels: decode and chunked prefill.

TPU adaptation of PagedAttention (Kwon et al.): instead of CUDA
pointer-chasing into the page pool, the page table is a **scalar-prefetch
operand** and ``BlockSpec.index_map`` selects the physical page for each grid
step — the Mosaic pipeline turns that into scheduled HBM->VMEM DMAs, which is
the TPU-native form of paged KV gather (see DESIGN.md §2). The pool is laid
out (P, Hkv, page_size, D), so each DMA moves one (page_size, D) tile of one
kv head: the last two block dims are then whole array dims, as Mosaic
requires (a (page_size, 1, D) block of a (P, page_size, Hkv, D) pool is
refused).

Decode: grid = (B, Hkv, max_pages); one query token per sequence; online
softmax accumulates in VMEM scratch over the page sweep; pages past a
sequence's length are skipped with ``pl.when`` (no DMA is wasted on them
either: their index map degrades to page 0 but the compute is skipped).

Chunked prefill: same grid, but each sequence contributes a *chunk* of
``chunk`` query tokens at absolute positions ``starts[b] + i``. The chunk's
own KV is already resident in the pool (the model writes it before
attending), so one page sweep serves both the history and the intra-chunk
causal triangle — there is no separate dense prefill cache and no
post-prefill scatter (see DESIGN.md §2). The (chunk, G) query axes are folded
into one VMEM row axis so GQA reuses each KV page DMA across the whole chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _paged_kernel(
    # scalar prefetch
    pt_ref,      # (B, max_pages) int32
    len_ref,     # (B,) int32
    # inputs
    q_ref,       # (1, 1, G, D)
    k_ref,       # (1, 1, page_size, D)
    v_ref,       # (1, 1, page_size, D)
    # outputs
    o_ref,       # (1, 1, G, D)
    # scratch
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    softcap: float,
    window: int,
    page_size: int,
    max_pages: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    base = p * page_size
    needed = base < length
    if window > 0:
        needed = jnp.logical_and(needed, base + page_size - 1 > length - 1 - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (page_size, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # (G, page_size)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < length
        if window > 0:
            mask = jnp.logical_and(mask, pos > length - 1 - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new[:, 0:1])
        pr = jnp.where(mask, pr, 0.0)
        l_scr[...] = alpha * l_prev + jnp.sum(pr, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, 0:1] + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(p == max_pages - 1)
    def _finalize():
        denom = l_scr[:, 0:1]
        out = jnp.where(denom > 0.0, acc_scr[...] / jnp.maximum(denom, 1e-30), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "window", "interpret"),
)
def paged_attention_pallas(
    q,            # (B, H, D)
    k_pages,      # (P, Hkv, page_size, D)
    v_pages,
    page_table,   # (B, max_pages) int32
    lengths,      # (B,) int32
    *,
    scale: float,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool = False,
):
    B, H, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    G = H // Hkv
    q4 = q.reshape(B, Hkv, G, D)

    kernel = functools.partial(
        _paged_kernel, scale=scale, softcap=softcap, window=window,
        page_size=ps, max_pages=maxp,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, p, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, D), lambda b, h, p, pt, ln: (pt[b, p], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, D), lambda b, h, p, pt, ln: (pt[b, p], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, p, pt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(page_table, lengths, q4, k_pages, v_pages)
    return out.reshape(B, H, D)


def _chunked_prefill_kernel(
    # scalar prefetch
    pt_ref,      # (B, max_pages) int32
    len_ref,     # (B,) int32     total resident kv (incl. this chunk)
    start_ref,   # (B,) int32     absolute position of the chunk's first token
    # inputs
    q_ref,       # (1, 1, C*G, D)
    k_ref,       # (1, 1, page_size, D)
    v_ref,       # (1, 1, page_size, D)
    # outputs
    o_ref,       # (1, 1, C*G, D)
    # scratch
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    softcap: float,
    window: int,
    page_size: int,
    max_pages: int,
    group: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    start = start_ref[b]
    base = p * page_size
    # pages at/after `length` hold no resident KV; causality never reaches
    # past the chunk end, and length == start + n_valid already covers that.
    needed = base < length
    if window > 0:
        needed = jnp.logical_and(needed, base + page_size - 1 > start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (C*G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (page_size, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # (C*G, page_size)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        kv_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        mask = jnp.logical_and(kv_pos < length, kv_pos <= q_pos)
        if window > 0:
            mask = jnp.logical_and(mask, kv_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new[:, 0:1])
        pr = jnp.where(mask, pr, 0.0)
        l_scr[...] = alpha * l_prev + jnp.sum(pr, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, 0:1] + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(p == max_pages - 1)
    def _finalize():
        denom = l_scr[:, 0:1]
        out = jnp.where(denom > 0.0, acc_scr[...] / jnp.maximum(denom, 1e-30), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "window", "interpret"),
)
def chunked_prefill_pallas(
    q,            # (B, C, H, D)  chunk of C query tokens per sequence
    k_pages,      # (P, Hkv, page_size, D)  pool, chunk KV already written
    v_pages,
    page_table,   # (B, max_pages) int32
    lengths,      # (B,) int32   resident kv entries incl. this chunk
    starts,       # (B,) int32   absolute position of q[:, 0]
    *,
    scale: float,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool = False,
):
    B, C, H, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    G = H // Hkv
    # fold (chunk, G) into one row axis per kv head: row c*G + g <-> (c, g)
    q4 = q.reshape(B, C, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, C * G, D)

    kernel = functools.partial(
        _chunked_prefill_kernel, scale=scale, softcap=softcap, window=window,
        page_size=ps, max_pages=maxp, group=G,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, C * G, D), lambda b, h, p, pt, ln, st: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, D), lambda b, h, p, pt, ln, st: (pt[b, p], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, D), lambda b, h, p, pt, ln, st: (pt[b, p], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C * G, D), lambda b, h, p, pt, ln, st: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * G, 128), jnp.float32),
            pltpu.VMEM((C * G, 128), jnp.float32),
            pltpu.VMEM((C * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, C * G, D), q.dtype),
        interpret=interpret,
    )(page_table, lengths, starts, q4, k_pages, v_pages)
    return out.reshape(B, Hkv, C, G, D).transpose(0, 2, 1, 3, 4).reshape(B, C, H, D)
