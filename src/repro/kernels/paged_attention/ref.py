"""Oracles for paged attention.

Decode (one query token per sequence; the oracle of a one-token chunk):
  q:          (B, H, D)
  k_pages:    (P, Hkv, page_size, D)   global physical page pool
  v_pages:    (P, Hkv, page_size, D)
  page_table: (B, max_pages)    int32 physical page id per logical page
  lengths:    (B,)              valid kv entries per sequence (incl. current)

Latent (absorbed MLA; one pool shared by every head):
  q:           (B, S, H, Dk)   absorbed queries
  pool:        (P, page_size, Dk)  each token's latent, then its rope key
  values:      the pool's first ``dv`` columns

Chunked prefill (a chunk of S query tokens per sequence, causal against the
KV already resident in the pool — which includes the chunk's own KV, written
by the caller before attending):
  q:           (B, S, H, D)
  q_positions: (B, S) int32    absolute position of each query token
  lengths:     (B,)            total resident kv entries (incl. this chunk)
"""
from __future__ import annotations

import jax.numpy as jnp


def gather_pages(pages, page_table):
    """(P, Hkv, ps, D) pool -> (B, maxp * ps, Hkv, D) per-sequence kv."""
    B, maxp = page_table.shape
    _, Hkv, ps, D = pages.shape
    g = pages[page_table].transpose(0, 1, 3, 2, 4)       # (B, maxp, ps, Hkv, D)
    return g.reshape(B, maxp * ps, Hkv, D)


def paged_attention_reference(
    q, k_pages, v_pages, page_table, lengths, *, scale=None, softcap: float = 0.0, window: int = 0
):
    B, H, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    group = H // Hkv
    if scale is None:
        scale = D ** -0.5

    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)

    s = jnp.einsum("bhd,bkhd->bhk", q, k, preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(maxp * ps)[None, :]
    mask = pos < lengths[:, None]
    if window > 0:
        mask &= pos > (lengths[:, None] - 1) - window
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask[:, None, :], p, 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhk,bkhd->bhd", (p / denom).astype(jnp.float32), v.astype(jnp.float32))
    return out.astype(q.dtype)


def chunked_prefill_reference(
    q, k_pages, v_pages, page_table, lengths, q_positions, *,
    scale=None, softcap: float = 0.0, window: int = 0,
):
    """Gather-based oracle for chunked paged prefill. Returns (B, S, H, D).

    Query token i of row b sits at absolute position q_positions[b, i] and
    attends causally to kv positions <= q_positions[b, i] (clipped to
    lengths[b]); rows where q_positions is past lengths produce zeros."""
    B, S, H, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    group = H // Hkv
    if scale is None:
        scale = D ** -0.5

    k = gather_pages(k_pages, page_table)
    v = gather_pages(v_pages, page_table)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)

    s = jnp.einsum("bshd,bkhd->bhsk", q, k, preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    kv_pos = jnp.arange(maxp * ps)[None, None, :]          # (1, 1, K)
    q_pos = q_positions[:, :, None]                        # (B, S, 1)
    mask = (kv_pos < lengths[:, None, None]) & (kv_pos <= q_pos)
    if window > 0:
        mask &= kv_pos > q_pos - window
    s = jnp.where(mask[:, None], s, -1e30)                 # (B, H, S, K)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask[:, None], p, 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhsk,bkhd->bshd", (p / denom).astype(jnp.float32),
                     v.astype(jnp.float32))
    return out.astype(q.dtype)


def mla_paged_reference(q, pool, page_table, lengths, q_positions, *, scale: float, dv: int):
    """Gather-based oracle for latent paged attention. Returns (B, S, H, dv):
    query token i of row b (every head) attends causally over the pool's
    rows, scored over all Dk columns, with the first ``dv`` as values."""
    B, maxp = page_table.shape
    _, ps, Dk = pool.shape
    kv = pool[page_table].reshape(B, maxp * ps, Dk)
    s = jnp.einsum("bshd,bkd->bhsk", q, kv, preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(maxp * ps)[None, None, :]
    mask = (kv_pos < lengths[:, None, None]) & (kv_pos <= q_positions[:, :, None])
    s = jnp.where(mask[:, None], s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask[:, None], p, 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhsk,bkd->bshd", p / denom, kv[..., :dv].astype(jnp.float32))
    return out.astype(q.dtype)
