"""Pallas TPU paged-attention kernels: chunked prefill over K/V pools, and
the latent sweep over one shared pool.

TPU adaptation of PagedAttention (Kwon et al.): the page table is a
**scalar-prefetch operand**, read on the TPU's scalar core to choose the
physical pages each step copies from HBM into VMEM (see DESIGN.md §2). The
pool is laid out (P, Hkv, page_size, D).

Chunked prefill (``chunked_prefill_pallas``, every attention call the
engine serves: prefill chunks, the decode sweep, verify widths): each
sequence contributes a *chunk* of ``chunk`` query tokens at absolute
positions ``starts[b] + i``. The chunk's own KV is already resident in the
pool (the model writes it before attending), so one page sweep serves both
the history and the intra-chunk causal triangle. The (chunk, G) query axes
fold into one row axis per kv head, so GQA reuses each page across the
whole chunk.

- Grid (B, Hkv // heads): one step per row and block of ``heads`` kv
  heads. A page's heads are contiguous in the pool, so one DMA copies a
  page for the whole head block.
- The step sweeps the row's blocks of ``ppb`` pages in a loop that stops
  at the row's resident length and starts at the block that holds its
  sliding window's first position; a padding row (length 0) copies and
  computes nothing and writes zeros. Only resident pages are copied, and
  V rows past the length are zeroed before P.V.
- The pool stays in HBM (``pl.ANY``); the step copies the pages itself
  into two VMEM buffers: it starts its first block's copies, and while
  block i computes, block i+1's copies fly. Heads and a block's page
  copies are loops, not unrolled, so the kernel lowers as fast as a
  one-page kernel: each step program that holds it lowers it at start-up.
- ``block_sizes`` derives ``ppb`` and ``heads`` from the call's shapes
  against ``VMEM_BUDGET``: 256-token blocks and all kv heads for the
  decode sweep, fewer heads for a wide prefill chunk.
- Scores take the operands in their own dtype (bf16 in serving) with f32
  accumulation; softmax, its running max and sum, and P.V stay in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


# The page buffers, query and output blocks and softmax state of one grid
# step stay under this many bytes of VMEM: v5e's default scoped limit is 16
# MiB, and Mosaic's own scratch needs the rest.
VMEM_BUDGET = 12 * 2**20
BLOCK_TOKENS = 256    # KV tokens a block of pages aims at


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(ppb, heads, page_size, rows, head_dim, kv_bytes, q_bytes):
    """VMEM one grid step of the chunked kernel holds, from its shapes."""
    bk, d = ppb * page_size, _pad(head_dim, 128)
    r = _pad(rows, 8)
    pages = 2 * 2 * ppb * heads * _pad(page_size, 32 // kv_bytes) * d * kv_bytes
    q_o = 2 * 2 * heads * _pad(rows, 32 // q_bytes) * d * q_bytes
    state = heads * r * (2 * 128 + d) * 4                 # m, l, acc in f32
    temps = 4 * r * _pad(bk, 128) * 4 + 2 * bk * d * 4    # scores, probs; V in f32
    return pages + q_o + state + temps


def block_sizes(page_size, max_pages, rows, n_kv, head_dim, kv_bytes, q_bytes):
    """(pages per block, kv heads per block) of a chunked-prefill call: blocks
    of ``BLOCK_TOKENS`` tokens (fewer where the table is shorter), and the
    most kv heads (a divisor of ``n_kv``) whose step fits ``VMEM_BUDGET``;
    the block halves only where one head does not fit."""
    ppb = max(1, min(max_pages, BLOCK_TOKENS // page_size))
    while True:
        for heads in range(n_kv, 0, -1):
            if n_kv % heads == 0 and _vmem_bytes(ppb, heads, page_size, rows, head_dim,
                                                 kv_bytes, q_bytes) <= VMEM_BUDGET:
                return ppb, heads
        if ppb == 1:
            return 1, 1
        ppb //= 2


def _chunked_prefill_kernel(
    # scalar prefetch
    pt_ref,      # (B * max_pages,) int32  page table, row-major
    len_ref,     # (B,) int32   total resident kv (incl. this chunk)
    start_ref,   # (B,) int32   absolute position of the chunk's first token
    # inputs
    q_ref,       # (1, heads, C*G, D)
    k_hbm,       # (P, Hkv, page_size, D)  pool, left in HBM
    v_hbm,
    # outputs
    o_ref,       # (1, heads, C*G, D)
    # scratch
    k_buf,       # (2, ppb, heads, page_size, D)  two blocks of pages
    v_buf,
    sems,        # DMA (2, 2): [K or V, buffer]
    m_scr, l_scr, acc_scr,   # (heads, C*G, 128), (heads, C*G, 128), (heads, C*G, D)
    *,
    scale: float,
    softcap: float,
    window: int,
    page_size: int,
    max_pages: int,
    group: int,
):
    b, hb = pl.program_id(0), pl.program_id(1)
    ppb, heads = k_buf.shape[1], k_buf.shape[2]
    bk = ppb * page_size
    d = q_ref.shape[3]
    length, start = len_ref[b], start_ref[b]
    n_pages = pl.cdiv(length, page_size)
    # the row's blocks of pages: up to its resident length, from the block
    # that holds its sliding window's first position
    lo = jnp.maximum(start - window + 1, 0) // bk if window > 0 else 0
    hi = pl.cdiv(length, bk)

    def dma(blk, slot, wait):
        """Start (or wait for) the copies of block ``blk``'s resident pages
        into buffer ``slot``: one copy per page and pool, each of the page's
        whole head block, which is contiguous."""
        def page(j, carry):
            phys = pt_ref[b * max_pages + blk * ppb + j]
            for i, (pool, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                copy = pltpu.make_async_copy(
                    pool.at[phys, pl.ds(hb * heads, heads)], buf.at[slot, j],
                    sems.at[i, slot])
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, n_pages - blk * ppb), page, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(lo < hi)
    def _first_block():
        dma(lo, 0, wait=False)

    def block(i, slot):
        base = i * bk

        def head(h, carry):
            q = q_ref[0, h]                                   # (C*G, D)
            k = k_buf[slot, :, h].reshape(bk, d)              # (bk, D)
            if q.dtype != k.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale                                         # (C*G, bk)
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            kv_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            q_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
            mask = jnp.logical_and(kv_pos < length, kv_pos <= q_pos)
            if window > 0:
                mask = jnp.logical_and(mask, kv_pos > q_pos - window)
            s = jnp.where(mask, s, NEG_INF)
            # pages past the row's length were not copied: their rows of the
            # buffer hold an older block, or nothing, and must not reach P.V
            live = base + jax.lax.broadcasted_iota(jnp.int32, (bk, d), 0) < length
            v = jnp.where(live, v_buf[slot, :, h].reshape(bk, d).astype(jnp.float32), 0.0)

            m_prev, l_prev = m_scr[h], l_scr[h]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(s - m_new[:, 0:1])
            pr = jnp.where(mask, pr, 0.0)
            l_scr[h] = alpha * l_prev + jnp.sum(pr, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha[:, 0:1] + jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_scr[h] = m_new
            return carry

        @pl.when(i + 1 < hi)
        def _next_block():
            dma(i + 1, 1 - slot, wait=False)

        dma(i, slot, wait=True)
        jax.lax.fori_loop(0, heads, head, 0)
        return 1 - slot

    jax.lax.fori_loop(lo, hi, block, 0)
    denom = l_scr[:, :, 0:1]
    out = jnp.where(denom > 0.0, acc_scr[...] / jnp.maximum(denom, 1e-30), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


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
    ppb, heads = block_sizes(ps, maxp, C * G, Hkv, D, k_pages.dtype.itemsize,
                             q.dtype.itemsize)
    # fold (chunk, G) into one row axis per kv head: row c*G + g <-> (c, g)
    q4 = q.reshape(B, C, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, C * G, D)

    kernel = functools.partial(
        _chunked_prefill_kernel, scale=scale, softcap=softcap, window=window,
        page_size=ps, max_pages=maxp, group=G,
    )
    qo_spec = pl.BlockSpec((1, heads, C * G, D), lambda b, h, *_: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv // heads),
        in_specs=[qo_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, heads, ps, D), k_pages.dtype),
            pltpu.VMEM((2, ppb, heads, ps, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((heads, C * G, 128), jnp.float32),
            pltpu.VMEM((heads, C * G, 128), jnp.float32),
            pltpu.VMEM((heads, C * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, C * G, D), q.dtype),
        # the TPU interpreter: uninitialized VMEM reads as NaN, and a copy
        # lands when it is waited for, as on the chip
        interpret=pltpu.InterpretParams() if interpret else False,
    )(page_table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      starts.astype(jnp.int32), q4, k_pages, v_pages)
    return out.reshape(B, Hkv, C, G, D).transpose(0, 2, 1, 3, 4).reshape(B, C, H, D)


# ------------------------------------------------------------------ latent
# Latent (``mla_paged_pallas``, every attention call the engine serves to a
# model of latent attention layers, ``models/mla.py``): one pool
# (P, page_size, Dk) of each token's latent and rope key, shared by all
# heads. The same page-block sweep as above, with the chunk's tokens and all
# heads folded into one row axis; each page is read once, scored over all Dk
# columns, and its first Dv columns (the latent) are the values. P.V takes P
# in the pool's dtype, with f32 accumulation.
def _mla_vmem_bytes(ppb, rows, page_size, dk, dv, kv_bytes, q_bytes):
    """VMEM one grid step of the latent kernel holds, from its shapes."""
    bk, dkp = ppb * page_size, _pad(dk, 128)
    r = _pad(rows, 8)
    pages = 2 * ppb * _pad(page_size, 32 // kv_bytes) * dkp * kv_bytes
    q_o = 2 * _pad(rows, 32 // q_bytes) * (dkp + dv) * q_bytes
    state = r * (2 * 128 + dv) * 4                        # m, l, acc in f32
    temps = 4 * r * _pad(bk, 128) * 4 + bk * dv * kv_bytes  # scores, probs; V
    return pages + q_o + state + temps


def mla_block_sizes(page_size, max_pages, chunk, heads, dk, dv, kv_bytes, q_bytes):
    """(pages per block, query tokens per row block) of a latent call:
    blocks of ``BLOCK_TOKENS`` tokens (fewer where the table is shorter),
    and the most query tokens (a divisor of ``chunk``) whose ``heads`` rows
    each fit ``VMEM_BUDGET``."""
    ppb = max(1, min(max_pages, BLOCK_TOKENS // page_size))
    for c in range(chunk, 0, -1):
        if chunk % c == 0 and _mla_vmem_bytes(ppb, c * heads, page_size, dk, dv,
                                              kv_bytes, q_bytes) <= VMEM_BUDGET:
            return ppb, c
    return ppb, 1


def _mla_kernel(
    # scalar prefetch
    pt_ref,      # (B * max_pages,) int32  page table, row-major
    len_ref,     # (B,) int32   total resident tokens (incl. this chunk)
    start_ref,   # (B,) int32   absolute position of the chunk's first token
    # inputs
    q_ref,       # (1, rows, Dk)  a block of the chunk's (token, head) rows
    pool_hbm,    # (P, page_size, Dk)  latent pool, left in HBM
    # outputs
    o_ref,       # (1, rows, Dv)
    # scratch
    buf,         # (2, ppb, page_size, Dk)  two blocks of pages
    sems,        # DMA (2,): one per buffer
    m_scr, l_scr, acc_scr,   # (rows, 128), (rows, 128), (rows, Dv)
    *,
    scale: float,
    page_size: int,
    max_pages: int,
    heads: int,
):
    b, rb = pl.program_id(0), pl.program_id(1)
    ppb = buf.shape[1]
    bk = ppb * page_size
    rows, dk = q_ref.shape[1], q_ref.shape[2]
    dv = o_ref.shape[2]
    length, start = len_ref[b], start_ref[b]
    # the block's last query sees no further than its own position
    end = jnp.minimum(length, start + ((rb + 1) * rows - 1) // heads + 1)
    n_pages = pl.cdiv(end, page_size)
    hi = pl.cdiv(end, bk)

    def dma(blk, slot, wait):
        """Start (or wait for) the copies of block ``blk``'s resident pages
        into buffer ``slot``, one whole page each."""
        def page(j, carry):
            phys = pt_ref[b * max_pages + blk * ppb + j]
            copy = pltpu.make_async_copy(pool_hbm.at[phys], buf.at[slot, j], sems.at[slot])
            copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, n_pages - blk * ppb), page, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(hi > 0)
    def _first_block():
        dma(0, 0, wait=False)

    def block(i, slot):
        base = i * bk

        @pl.when(i + 1 < hi)
        def _next_block():
            dma(i + 1, 1 - slot, wait=False)

        dma(i, slot, wait=True)
        q = q_ref[0]                                          # (rows, Dk)
        kv = buf[slot].reshape(bk, dk)                        # (bk, Dk)
        if q.dtype != kv.dtype:
            q, kv = q.astype(jnp.float32), kv.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                             # (rows, bk)
        kv_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = start + (rb * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)) // heads
        mask = jnp.logical_and(kv_pos < length, kv_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)
        # pages past ``end`` were not copied: their rows hold an older
        # block, or nothing, and must not reach P.V
        live = base + jax.lax.broadcasted_iota(jnp.int32, (bk, dv), 0) < end
        v = jnp.where(live, kv[:, :dv], jnp.zeros((), kv.dtype))   # the latent

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.where(mask, jnp.exp(s - m_new[:, 0:1]), 0.0)
        l_scr[...] = alpha * l_prev + jnp.sum(pr, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, 0:1] + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        return 1 - slot

    jax.lax.fori_loop(0, hi, block, 0)
    denom = l_scr[:, 0:1]
    out = jnp.where(denom > 0.0, acc_scr[...] / jnp.maximum(denom, 1e-30), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "dv", "interpret"))
def mla_paged_pallas(
    q,            # (B, C, H, Dk)  absorbed queries: latent part, then rope part
    pool,         # (P, page_size, Dk)  latent pool, chunk rows already written
    page_table,   # (B, max_pages) int32
    lengths,      # (B,) int32   resident tokens incl. this chunk
    starts,       # (B,) int32   absolute position of q[:, 0]
    *,
    scale: float,
    dv: int,
    interpret: bool = False,
):
    """Latent paged attention (absorbed MLA): every head of every query
    token scores the pool's rows over all ``Dk`` columns, and P.V runs over
    the first ``dv`` (the latent itself). Returns (B, C, H, dv).

    The chunk's tokens and heads fold into one row axis, row c*H + h, so a
    page is read once for all heads; grid (B, row blocks), each step
    sweeping the row's pages in blocks of ``ppb`` with two buffers and
    stopping at the resident length (or its block's last query), as
    ``chunked_prefill_pallas`` does."""
    B, C, H, Dk = q.shape
    P, ps, _ = pool.shape
    maxp = page_table.shape[1]
    ppb, cb = mla_block_sizes(ps, maxp, C, H, Dk, dv, pool.dtype.itemsize, q.dtype.itemsize)
    rows = cb * H
    kernel = functools.partial(_mla_kernel, scale=scale, page_size=ps, max_pages=maxp,
                               heads=H)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, C // cb),
        in_specs=[pl.BlockSpec((1, rows, Dk), lambda b, r, *_: (b, r, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows, dv), lambda b, r, *_: (b, r, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, Dk), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C * H, dv), q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(page_table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      starts.astype(jnp.int32), q.reshape(B, C * H, Dk), pool)
    return out.reshape(B, C, H, dv)
