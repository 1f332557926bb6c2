"""GQA attention sublayer: train / prefill / chunk (paged serving) / decode
(dense ring-buffer cache or paged cache) / cross-attention. One code path per
mode, shared projections.

Cache formats (per layer, unstacked — the scan adds the leading layers dim):
  dense: {"k": (B, W, Hkv, hd), "v": ..., "slot_pos": (B, W) int32}
         W = min(max_seq, window) — a ring buffer; slot_pos holds the absolute
         position stored in each slot (-1 = empty). Full attention is W=max_seq
         (slot == position) through the same code.
  paged: {"kp": (P, Hkv, ps, hd), "vp": ...} + engine-level page_table/lengths.
  cross: {"ck": (B, M, Hkv, hd), "cv": ...} built once at prefill.

Mode "chunk" is the serving engine's unified iteration (DESIGN.md §2): each
batch row carries a chunk of S tokens of one sequence (S == 1 is decode); KV
is written straight into the paged pool (no dense intermediate) and queries
attend causally over the pool, which already contains the chunk itself.
``chunk`` carries {"slots", "nvalid", "first", "valid"} — the engine-slot id,
the per-row count of live tokens, whether this is the row's first chunk, and
the per-position validity mask (invalid positions write to null page 0).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (chunked_prefill_attention,
                                           paged_attention)
from repro.models.common import RunCtx, rope, shard_act


def _project_qkv(p, h, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dnk->bsnk", h, p["wk"])
    v = jnp.einsum("bsd,dnk->bsnk", h, p["wv"])
    if "bq" in p:
        q = q + p["bq"][None, None]
        k = k + p["bk"][None, None]
        v = v + p["bv"][None, None]
    return q, k, v


def _out_proj(p, o):
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _decode_dense_attn(q, cache, positions, *, window: int, softcap: float, scale: float):
    """q: (B,1,H,hd); ring-buffer cache. Plain einsum (q len 1 needs no tiling);
    shards under GSPMD, incl. seq-sharded caches (softmax combine collectives)."""
    k, v, slot_pos = cache["k"], cache["v"], cache["slot_pos"]
    B, W, Hkv, hd = k.shape
    H = q.shape[2]
    G = H // Hkv
    q5 = q.reshape(B, 1, Hkv, G, hd)
    s = jnp.einsum("bqngd,bsnd->bnqgs", q5, k, preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    pos = positions[:, None]                       # (B,1) current absolute position
    ok = (slot_pos >= 0) & (slot_pos <= pos)
    if window > 0:
        ok &= slot_pos > pos - window
    s = jnp.where(ok[:, None, None, None, :], s, -1e30)
    p_attn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnqgs,bsnd->bqngd", p_attn.astype(jnp.float32), v.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def _write_ring(cache, k, v, positions):
    """Scatter new kv at positions into the ring buffer. decode: k (B,1,Hkv,hd),
    positions (B,). prefill: k (B,S,...), positions (S,) shared across batch."""
    W = cache["k"].shape[1]
    if k.shape[1] == 1 and positions.ndim == 1 and positions.shape[0] == k.shape[0]:
        slots = positions % W                       # (B,)
        b_idx = jnp.arange(k.shape[0])
        new_k = cache["k"].at[b_idx, slots].set(k[:, 0])
        new_v = cache["v"].at[b_idx, slots].set(v[:, 0])
        new_sp = cache["slot_pos"].at[b_idx, slots].set(positions)
    else:                                           # prefill: positions (S,)
        S = k.shape[1]
        if S > W:                                   # keep the last W tokens
            k, v, positions = k[:, -W:], v[:, -W:], positions[-W:]
        slots = positions % W
        new_k = cache["k"].at[:, slots].set(k)
        new_v = cache["v"].at[:, slots].set(v)
        new_sp = cache["slot_pos"].at[:, slots].set(positions[None, :])
    return {"k": new_k, "v": new_v, "slot_pos": new_sp}


def _write_paged(cache, k, v, positions, page_table):
    """k (B,1,Hkv,hd); positions (B,) absolute; page_table (B, maxp)."""
    ps = cache["kp"].shape[2]
    b_idx = jnp.arange(k.shape[0])
    logical = positions // ps
    slot = positions % ps
    phys = page_table[b_idx, logical]
    return {
        "kp": cache["kp"].at[phys, :, slot].set(k[:, 0].astype(cache["kp"].dtype)),
        "vp": cache["vp"].at[phys, :, slot].set(v[:, 0].astype(cache["vp"].dtype)),
    }


def paged_slots(positions, page_table, valid, page_size: int):
    """(physical page, slot in it) of every position of a chunk, flattened:
    positions (B, S) absolute; valid (B, S). Invalid positions are routed to
    the reserved null page 0 (the allocator never hands it out), so one
    fixed-shape scatter serves ragged chunks."""
    maxp = page_table.shape[1]
    logical = jnp.clip(positions // page_size, 0, maxp - 1)
    phys = jnp.where(valid, jnp.take_along_axis(page_table, logical, axis=1), 0)
    slot = positions % page_size
    return phys.reshape(-1), slot.reshape(-1)


def _write_paged_chunk(cache, k, v, positions, page_table, valid):
    """Scatter a whole chunk's KV into the paged pool in one shot.

    k/v (B, S, Hkv, hd); positions (B, S) absolute; valid (B, S)."""
    B, S = positions.shape
    pf, sf = paged_slots(positions, page_table, valid, cache["kp"].shape[2])
    kf = k.reshape(B * S, *k.shape[2:]).astype(cache["kp"].dtype)
    vf = v.reshape(B * S, *v.shape[2:]).astype(cache["vp"].dtype)
    return {
        "kp": cache["kp"].at[pf, :, sf].set(kf),
        "vp": cache["vp"].at[pf, :, sf].set(vf),
    }


def attention_sublayer(
    p: Dict[str, Any],
    h,                       # normed input (B, S, d)
    ctx: RunCtx,
    cfg: ModelConfig,
    kind: str,               # 'A' | 'L' | 'G' | 'X' (cross) | 'E' (encoder, bidirectional)
    cache: Optional[Dict[str, Any]] = None,
    positions=None,          # decode: (B,) abs position of the new token;
                             # prefill: (S,); chunk: (B, S) absolute
    memory=None,             # cross: encoder output (B, M, d)
    page_table=None,
    lengths=None,
    chunk=None,              # chunk mode: {"slots", "nvalid", "first", "valid"}
):
    """Returns (attn_out (B,S,d), new_cache)."""
    window = cfg.sliding_window if kind == "L" else 0
    softcap = cfg.attn_softcap
    scale = cfg.head_dim ** -0.5
    B, S, _ = h.shape

    # ---------------- cross attention ----------------
    if kind == "X":
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        if ctx.mode == "chunk":
            # slot-pooled cross cache: rows map to engine slots. With encoder
            # memory supplied (prefill chunks) recompute ck/cv and persist
            # them at the row's slot; without (decode sweep) read the slot.
            slots, row_valid = chunk["slots"], chunk["nvalid"] > 0
            if memory is not None:
                ck = jnp.einsum("bmd,dnk->bmnk", memory, p["wk"])
                cv = jnp.einsum("bmd,dnk->bmnk", memory, p["wv"])
                new_cache = {
                    "ck": cache["ck"].at[slots].set(
                        jnp.where(row_valid[:, None, None, None],
                                  ck.astype(cache["ck"].dtype), cache["ck"][slots])),
                    "cv": cache["cv"].at[slots].set(
                        jnp.where(row_valid[:, None, None, None],
                                  cv.astype(cache["cv"].dtype), cache["cv"][slots])),
                }
            else:
                ck, cv = cache["ck"][slots], cache["cv"][slots]
                new_cache = cache
        elif cache is not None and ctx.mode == "decode":
            ck, cv = cache["ck"], cache["cv"]
            new_cache = cache
        else:
            ck = jnp.einsum("bmd,dnk->bmnk", memory, p["wk"])
            cv = jnp.einsum("bmd,dnk->bmnk", memory, p["wv"])
            new_cache = {"ck": ck, "cv": cv} if cache is not None else None
        o = flash_attention(
            q, ck, cv, causal=False, softcap=softcap, scale=scale,
            backend=ctx.attn_backend, interpret=ctx.interpret,
            block_q=ctx.block_q, block_kv=ctx.block_kv, unroll=ctx.attn_unroll,
        )
        return _out_proj(p, o), new_cache

    q, k, v = _project_qkv(p, h, cfg)

    if ctx.mode == "chunk" and kind != "E":   # encoder runs full-seq below
        # serving chunk: write this chunk's KV straight into the paged pool,
        # then attend causally over the pool (history + the chunk itself).
        # Rows carrying a vision patch prefix have non-affine positions,
        # which the pallas kernel cannot represent — force the xla gather.
        backend = "xla" if chunk.get("prefix") else ctx.attn_backend
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        new_cache = _write_paged_chunk(cache, k, v, positions, page_table,
                                       chunk["valid"])
        o = chunked_prefill_attention(
            q, new_cache["kp"], new_cache["vp"], page_table, lengths, positions,
            scale=scale, softcap=softcap, window=window,
            backend=backend, interpret=ctx.interpret,
        )
        return _out_proj(p, o), new_cache

    if ctx.mode == "decode":
        q = rope(q, positions[:, None], cfg.rope_theta)   # (B,1,...)
        k = rope(k, positions[:, None], cfg.rope_theta)
        if cache is not None and "kp" in cache:           # paged
            new_cache = _write_paged(cache, k, v, positions, page_table)
            o = paged_attention(
                q[:, 0], new_cache["kp"], new_cache["vp"], page_table, lengths,
                scale=scale, softcap=softcap, window=window,
                backend=ctx.attn_backend, interpret=ctx.interpret,
            )[:, None]                                     # (B,1,H,hd)
        else:                                              # dense ring cache
            new_cache = _write_ring(cache, k, v, positions)
            o = _decode_dense_attn(q, new_cache, positions, window=window,
                                   softcap=softcap, scale=scale)
        return _out_proj(p, o), new_cache

    # ---------------- train / prefill / encoder ----------------
    if positions is None:
        positions = jnp.arange(S)
    causal = kind != "E"
    q = rope(q, positions, cfg.rope_theta)
    k_roped = rope(k, positions, cfg.rope_theta)
    # Megatron-SP placement: when sequence parallelism is active, the residual
    # stream stays seq-sharded BETWEEN layers; q/k must be whole-sequence here
    # (logical None on seq => GSPMD inserts the all-gather at the projection
    # and the reduce-scatter after the out-projection).
    q = shard_act(q, ("batch", None, "heads", None))
    k_roped = shard_act(k_roped, ("batch", None, "kv_heads", None))
    o = flash_attention(
        q, k_roped, v, causal=causal, window=window, softcap=softcap, scale=scale,
        backend=ctx.attn_backend, interpret=ctx.interpret,
        block_q=ctx.block_q, block_kv=ctx.block_kv, unroll=ctx.attn_unroll,
    )
    new_cache = cache
    if cache is not None and "k" in cache:                 # prefill: persist kv
        new_cache = _write_ring(cache, k_roped, v, positions)
    return _out_proj(p, o), new_cache
