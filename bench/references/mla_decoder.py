"""Plain float32 forward pass of a decoder of latent-attention layers with
sparse experts (DeepSeek-V2-Lite), for checking what the served path
produced.

It follows the published equations (arXiv:2405.04434, no query LoRA):
RMSNorm; per token q = h·Wq split into q_nope and q_pe, [c, k_pe] = h·Wkv_a
with c RMS-normed (eps 1e-6); YaRN rotary positions (split halves) on q_pe
and on k_pe, one head shared by all; each head's key [c·Wuk, k_pe] and value
c·Wuv, a causal softmax scaled by mscale² / √(nope + rope), and
Σ_heads (P·v)·Wo: the expanded form, with nothing cached. The experts are
``moe_decoder``'s (SwiGLU experts, shared experts, the dense first layer,
the untied head), routed by a softmax over all experts, top-k, the k
weights renormalized only where ``norm_topk_prob`` says so. Every matrix
product runs in float32 at ``HIGHEST`` precision over the whole sequence,
one sequence at a time, attention in blocks of queries; no cache, no
kernel, nothing of the program.

It reads the weights the benchmark made: ``attn`` {wq (d,H,nope+rope),
wkv_a (d,r+rope), kv_norm {w (r,)}, wk_b (r,H,nope), wv_b (r,H,dv), wo
(H,dv,d)} and ``moe_decoder``'s layout for the rest.

``quant="fp8"`` is the control: every matrix stored in float8 (e4m3) with a
scale per output channel, then computed in float32 as above.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.arch.mla_moe_decoder import widths
from bench.references.moe_decoder import (F32, HI, _bucket, _dense_in, _expert, _layers,
                                          _logits, _rms, _swiglu, _w)

QBLOCK = 512          # queries a block of the attention scores holds
SEGMENT = 4096        # a sequence is padded to a multiple of this many rows, or of
                      # the engine's max_seq in whole blocks where that is shorter
LATENT_EPS = 1e-6


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(cfg: dict, S: int, dim: int):
    """(cos, sin), each (S, dim // 2): YaRN where ``rope_scaling`` says so."""
    w, y = widths(cfg), cfg.get("rope_scaling") or {}
    half = dim // 2
    freqs = w["theta"] ** (-np.arange(half, dtype=np.float64) / half)
    scale = 1.0
    if y:
        def pair(rot):
            return dim * math.log(y["original_max_position_embeddings"]
                                  / (rot * 2 * math.pi)) / (2 * math.log(w["theta"]))
        low = max(math.floor(pair(y["beta_fast"])), 0)
        high = min(math.ceil(pair(y["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
        freqs = freqs * (1.0 - ramp) + freqs / y["factor"] * ramp
        scale = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"], y["mscale_all_dim"])
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
    return (jnp.asarray(np.cos(ang) * scale, F32), jnp.asarray(np.sin(ang) * scale, F32))


def softmax_scale(cfg: dict) -> float:
    w, y = widths(cfg), cfg.get("rope_scaling") or {}
    s = (w["nope"] + w["rope"]) ** -0.5
    if y.get("mscale_all_dim"):
        s *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return s


def _rope(x, cos, sin):
    """x (S, heads, dim), split halves."""
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


@functools.partial(jax.jit, static_argnames=("nope", "scale", "eps", "quant"),
                   donate_argnums=(0,))
def _attention(x, n, cos, sin, ln, wq, wkv_a, kv_norm, wk_b, wv_b, wo, r, *,
               nope, scale, eps, quant):
    """One sequence's rows x (Sp, d), the first ``n`` real, through latent
    attention of stacked layer ``r``, residual added."""
    ln, wq, wkv_a, kv_norm = ln[r], wq[r], wkv_a[r], kv_norm[r]
    wk_b, wv_b, wo = wk_b[r], wv_b[r], wo[r]
    rank = wk_b.shape[0]
    h = _rms(x, ln, eps)
    q = jnp.einsum("sd,dhk->shk", h, _w(wq, quant, 0), precision=HI)
    kv = jnp.dot(h, _w(wkv_a, quant, 0), precision=HI)
    c = _rms(kv[:, :rank], kv_norm, LATENT_EPS)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cos, sin)
    k_pe = _rope(kv[:, None, rank:], cos, sin)[:, 0]
    k_nope = jnp.einsum("sr,rhk->shk", c, _w(wk_b, quant, 0), precision=HI)
    v = jnp.einsum("sr,rhk->shk", c, _w(wv_b, quant, 0), precision=HI)
    Sp = x.shape[0]
    kv_pos = jnp.arange(Sp)

    def block(b):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, b, QBLOCK)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, b, QBLOCK)
        s = (jnp.einsum("qhk,shk->hqs", qn, k_nope, precision=HI)
             + jnp.einsum("qhk,sk->hqs", qp, k_pe, precision=HI)) * scale
        q_pos = b + jnp.arange(QBLOCK)
        ok = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos[None, :] < n)
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shk->qhk", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(0, Sp, QBLOCK)).reshape(Sp, *v.shape[1:])
    return x + jnp.einsum("qhk,hkd->qd", o, _w(wo, quant, (0, 1)), precision=HI)


@functools.partial(jax.jit, static_argnames=("k", "eps", "norm"))
def _route(x, ln, router, r, *, k, eps, norm):
    """Normed input, the k weights (renormalized where ``norm``) and experts
    of each row, and how far the k-th expert's probability lies above the
    next one's."""
    h = _rms(x, ln[r], eps)
    probs = jax.nn.softmax(jnp.dot(h, router[r].astype(F32), precision=HI), axis=-1)
    topw, topi = jax.lax.top_k(probs, k + 1)
    margin = topw[:, k - 1] - topw[:, k]
    topw, topi = topw[:, :k], topi[:, :k]
    if norm:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    return h, topw, topi, margin


def _sequence(params, cfg: dict, seq: np.ndarray, quant: str):
    """(rows (Sp, d) after the last layer, least routing margin per row)."""
    w = widths(cfg)
    n = len(seq)
    seg = min(SEGMENT, -(-cfg["engine"]["max_seq"] // QBLOCK) * QBLOCK)
    Sp = -(-n // seg) * seg
    tokens = np.zeros(Sp, np.int32)
    tokens[:n] = seq
    cos, sin = rope_tables(cfg, Sp, w["rope"])
    X = params["embed"]["w"][jnp.asarray(tokens)].astype(F32)
    least = np.full(Sp, np.inf, np.float32)
    scale = softmax_scale(cfg)
    for p, r in _layers(params):
        a = p["attn"]
        X = _attention(X, n, cos, sin, p["ln1"], a["wq"], a["wkv_a"], a["kv_norm"]["w"],
                       a["wk_b"], a["wv_b"], a["wo"], r, nope=w["nope"], scale=scale,
                       eps=w["eps"], quant=quant)
        if "moe" in p:
            m = p["moe"]
            h, topw, topi, margin = _route(X, p["ln2"], m["router"], r, k=w["k"],
                                           eps=w["eps"], norm=w["norm_topk"])
            least = np.minimum(least, np.asarray(margin))
            topi_h, topw_h = np.asarray(topi)[:n], np.asarray(topw)[:n]
            y = jnp.zeros_like(X)
            for e in range(w["E"]):
                tok, slot = np.nonzero(topi_h == e)
                if not len(tok):
                    continue
                B = _bucket(len(tok))
                idx = np.full(B, Sp, np.int32)
                gate = np.zeros(B, np.float32)
                idx[:len(tok)], gate[:len(tok)] = tok, topw_h[tok, slot]
                y = _expert(y, h, idx, gate, m["wg"], m["wu"], m["wd"], r, e, quant=quant)
            if "shared" in m:
                s = m["shared"]
                y = y + _swiglu(h, s["wi"], s["wg"], s["wo"], r, quant=quant)
            X = X + y
        else:
            d = p["mlp"]
            X = X + _swiglu(_dense_in(X, p["ln2"], r, eps=w["eps"]), d["wi"], d["wg"], d["wo"],
                            r, quant=quant)
    return X, least


def forward(params, cfg: dict, seqs: Sequence[np.ndarray], keep: Sequence[int],
            quant: str = "none", margins: bool = False):
    """Logits (float32, on the host) of each sequence at its last
    ``keep[i]`` positions: row j scores the token after position
    ``len(seq) - keep[i] + j``. With ``margins``, also the routing margin
    of the same positions: the least, over the MoE layers, of how far the
    k-th expert's router probability lies above the next one's.

    Each sequence runs alone, padded to a multiple of ``SEGMENT`` rows (or
    of a shorter ``max_seq``), so the programs see a few shapes whatever
    the lengths; padding rows are
    routed to no expert and masked out of every real row's attention."""
    out, near = [], []
    for seq, k in zip(seqs, keep):
        n = len(seq)
        X, least = _sequence(params, cfg, np.asarray(seq, np.int32), quant)
        near.append(least[n - k:n])
        B = _bucket(k)
        rows = np.full(B, n - 1, np.int32)
        rows[:k] = np.arange(n - k, n)
        lg = _logits(X, rows, params["final_norm"]["w"], params["lm_head"]["w"],
                     eps=widths(cfg)["eps"], quant=quant)
        out.append(np.asarray(lg)[:k])
        del X
    return (out, near) if margins else out
