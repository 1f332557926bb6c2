"""GQA attention sublayer, in two modes with shared projections:

  full sequence: no cache (train, ``LM.forward``, the encoder, and the
      cross-attention of an encoder-decoder); causal or bidirectional.
  chunk: the serving engine's one iteration (``LM.decode_chunk``, DESIGN.md
      §2). Each batch row carries a chunk of S tokens of one sequence (S == 1
      is decode); its KV is written straight into the paged pool and its
      queries attend causally over the pool, which already holds the chunk
      itself. ``chunk`` is the pack {"slots", "nvalid", "first", "valid",
      "prefix"}: the engine-slot id, the per-row count of live tokens,
      whether this is the row's first chunk, the per-position validity mask
      (invalid positions write to null page 0), and whether rows carry a
      vision patch prefix.

Cache formats (per layer, unstacked — the scan adds the leading layers dim):
  paged: {"kp": (P, Hkv, ps, hd), "vp": ...} + the engine's page_table/lengths.
  cross: {"ck": (slots, M, Hkv, hd), "cv": ...}, one slot per engine slot,
         filled by the slot's prefill chunks.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import chunked_prefill_attention
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
    positions=None,          # chunk: (B, S) absolute; full sequence: (S,) or None
    memory=None,             # cross: encoder output (B, M, d)
    page_table=None,
    lengths=None,
    chunk=None,              # the chunk pack; None: the full sequence
):
    """Returns (attn_out (B,S,d), new_cache); new_cache is None for the full
    sequence."""
    window = cfg.sliding_window if kind == "L" else 0
    softcap = cfg.attn_softcap
    scale = cfg.head_dim ** -0.5
    B, S, _ = h.shape

    # ---------------- cross attention ----------------
    if kind == "X":
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        if chunk is not None:
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
        else:
            ck = jnp.einsum("bmd,dnk->bmnk", memory, p["wk"])
            cv = jnp.einsum("bmd,dnk->bmnk", memory, p["wv"])
            new_cache = None
        o = flash_attention(
            q, ck, cv, causal=False, softcap=softcap, scale=scale,
            backend=ctx.attn_backend, interpret=ctx.interpret,
            block_q=ctx.block_q, block_kv=ctx.block_kv, unroll=ctx.attn_unroll,
        )
        return _out_proj(p, o), new_cache

    q, k, v = _project_qkv(p, h, cfg)

    if chunk is not None:
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

    # ---------------- full sequence: train / forward / encoder ----------------
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
    return _out_proj(p, o), None
