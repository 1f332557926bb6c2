"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434), without
a query LoRA. For each token with normed input h:

  q = h·Wq -> (H, nope + rope), split into q_nope and q_pe
  [c, k_pe] = h·Wkv_a, c = RMSNorm(c)          (latent and rope key, one head)
  q_pe, k_pe get rope (YaRN where the config has it)
  per head: k_nope = c·Wuk, v = c·Wuv
  score = (q_nope·k_nope + q_pe·k_pe) · s, causal softmax, s = mscale² / √(nope + rope)
  out = Σ_heads (P·v)·Wo

Serving (``LM.decode_chunk``) caches [c, k_pe] — ``MLAConfig.cache_dim``
values a token, shared by every head — in one paged pool per layer, and
attends in the absorbed form: q_lat = q_nope·Wukᵀ joined with q_pe scores
the pool's rows directly, o_lat = P·c, and out = (o_lat·Wuv)·Wo. The full sequence
with no cache (train, the forward pass) uses the expanded form in plain XLA.

A pool row is ``pool_width`` wide: the cached values, then zeros up to a
whole number of 128-lane tiles (576 -> 640). A TPU lays a 576-wide array
out in 640 lanes anyway, and a kernel may copy only whole tiles of a row,
so the pool holds what the chip would allocate, and the zeros score
nothing.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import mla_paged_attention
from repro.models.attention import paged_slots
from repro.models.common import RunCtx, rmsnorm, rope, yarn_mscale

LATENT_NORM_EPS = 1e-6
LANES = 128


def pool_width(cfg: ModelConfig) -> int:
    """Columns of a latent pool row: the cached values, padded to whole tiles."""
    return -(-cfg.mla.cache_dim // LANES) * LANES


def _pad_to(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def softmax_scale(cfg: ModelConfig) -> float:
    """1/√(q head dim), times YaRN's mscale(mscale_all_dim) squared."""
    m, y = cfg.mla, cfg.rope_yarn
    s = m.q_head_dim ** -0.5
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def project(p, h, positions, cfg: ModelConfig):
    """(q_nope (B,S,H,nope), q_pe (B,S,H,rope), c (B,S,r), k_pe (B,S,rope)):
    the queries and the latent row a token caches, roped."""
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    kv = jnp.einsum("bsd,dk->bsk", h, p["wkv_a"])
    c = rmsnorm(kv[..., :m.kv_lora_rank], p["kv_norm"]["w"], LATENT_NORM_EPS)
    q_pe = rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta, cfg.rope_yarn)
    k_pe = rope(kv[:, :, None, m.kv_lora_rank:], positions, cfg.rope_theta, cfg.rope_yarn)
    return q[..., :m.qk_nope_head_dim], q_pe, c, k_pe[:, :, 0]


def _write_latent_chunk(pool, rows, positions, page_table, valid):
    """Scatter a chunk's latent rows (B, S, Dk) into the pool (P, ps, Dk)."""
    pf, sf = paged_slots(positions, page_table, valid, pool.shape[1])
    return pool.at[pf, sf].set(rows.reshape(pf.shape[0], -1).astype(pool.dtype))


def expanded_attention(q_nope, q_pe, c, k_pe, p, scale: float):
    """Causal attention over a whole sequence in the expanded form, f32
    softmax: (B, S, H, v_head_dim)."""
    k_nope = jnp.einsum("bsr,rhk->bshk", c, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c, p["wv_b"])
    s = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope, preferred_element_type=jnp.float32)
         + jnp.einsum("bqhk,bsk->bhqs", q_pe, k_pe, preferred_element_type=jnp.float32))
    S = q_nope.shape[1]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    pr = jax.nn.softmax(jnp.where(causal, s * scale, -1e30), axis=-1)
    return jnp.einsum("bhqs,bshk->bqhk", pr, v.astype(jnp.float32)).astype(q_nope.dtype)


def absorbed_query(q_nope, q_pe, p, width: int):
    """The query a latent pool row is scored against: q_nope·Wukᵀ (per
    head, latent-wide), then q_pe, then zeros to ``width``. (B, S, H, width)."""
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])
    return _pad_to(jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1), width)


def mla_sublayer(
    p: Dict[str, Any],
    h,                       # normed input (B, S, d)
    ctx: RunCtx,
    cfg: ModelConfig,
    cache: Optional[Dict[str, Any]] = None,
    positions=None,          # chunk: (B, S) absolute; full sequence: (S,)
    page_table=None,
    lengths=None,
    chunk=None,              # the chunk pack ({"valid", ...}); None: the full sequence
):
    """Returns (attn_out (B,S,d), new_cache); a chunk's cache is
    {"lat": (P, page_size, pool_width)}, the full sequence's None."""
    with jax.named_scope("mla"):
        m = cfg.mla
        scale = softmax_scale(cfg)
        if positions is None:
            positions = jnp.arange(h.shape[1])
        q_nope, q_pe, c, k_pe = project(p, h, positions, cfg)
        if chunk is not None:
            width = cache["lat"].shape[-1]
            rows = _pad_to(jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1), width)
            lat = _write_latent_chunk(cache["lat"], rows, positions, page_table,
                                      chunk["valid"])
            o_lat = mla_paged_attention(
                absorbed_query(q_nope, q_pe, p, width), lat, page_table, lengths, positions,
                scale=scale, dv=m.kv_lora_rank,
                backend=ctx.attn_backend, interpret=ctx.interpret)
            o = jnp.einsum("bshr,rhk->bshk", o_lat, p["wv_b"])
            new_cache = {"lat": lat}
        else:
            o = expanded_attention(q_nope, q_pe, c, k_pe, p, scale)
            new_cache = None
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), new_cache
