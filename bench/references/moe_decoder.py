"""Plain float32 forward pass of a decoder with sparse experts (Mixtral,
DeepSeek-MoE), for checking what the served path produced.

It follows the published equations: RMSNorm; attention with rotary
positions (split halves), grouped KV heads and a causal softmax; a router
softmax over all experts, top-k, the k weights renormalized; SwiGLU experts,
SwiGLU shared experts and dense layers; an untied LM head. Every matrix
product runs in float32 at ``HIGHEST`` precision over the whole sequence,
with no cache, no batching across sequences in attention and no kernel.

It imports nothing of the program. It reads the weights the benchmark made
(``bench.model.make_weights``) through the parameter tree's layout: layer
groups of stacked layers, ``attn`` {wq (d,H,hd), wk, wv (d,Hkv,hd), wo
(H,hd,d)}, ``moe`` {router (d,E), wg, wu (E,d,f), wd (E,f,d), shared {wi, wg
(d,fs), wo (fs,d)}}, ``mlp`` {wi, wg, wo}, ``ln1``, ``ln2``, ``final_norm``,
``embed`` (V,d), ``lm_head`` (d,V).

``quant="fp8"`` is the control: every matrix of the attention, the experts,
the dense layers and the head stored in float8 (e4m3) with a scale per
output channel, and then computed in float32 as above.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.arch.moe_decoder import widths

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
QBLOCK = 512


def _w(x, quant: str, axes) -> jnp.ndarray:
    """A weight in float32, or its float8 copy with a scale per output
    channel (the max over the contracted ``axes``)."""
    x = x.astype(F32)
    if quant != "fp8":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("Sp", "theta", "eps", "quant"),
                   donate_argnums=(0,))
def _attention(X, start, n, ln, wq, wk, wv, wo, r, *, Sp, theta, eps, quant):
    """One sequence, rows [start, start + n) of X, attends causally over
    itself in stacked layer ``r``; X's rows [start, start + Sp) are updated
    (its padding rows too)."""
    ln, wq, wk, wv, wo = ln[r], wq[r], wk[r], wv[r], wo[r]
    x = jax.lax.dynamic_slice_in_dim(X, start, Sp)
    h = _rms(x, ln, eps)
    q = _rope(jnp.einsum("sd,dhk->shk", h, _w(wq, quant, 0), precision=HI), theta)
    k = _rope(jnp.einsum("sd,dhk->shk", h, _w(wk, quant, 0), precision=HI), theta)
    v = jnp.einsum("sd,dhk->shk", h, _w(wv, quant, 0), precision=HI)
    G = q.shape[1] // k.shape[1]                # query head h reads kv head h // G
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    kv_pos = jnp.arange(Sp)
    out = []
    for b in range(0, Sp, QBLOCK):              # query blocks bound the score size
        qb = q[b:b + QBLOCK]
        s = jnp.einsum("qhk,shk->hqs", qb, k, precision=HI) * q.shape[-1] ** -0.5
        q_pos = b + jnp.arange(qb.shape[0])
        ok = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos[None, :] < n)
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqs,shk->qhk", p, v, precision=HI))
    o = jnp.concatenate(out)
    x = x + jnp.einsum("qhk,hkd->qd", o, _w(wo, quant, (0, 1)), precision=HI)
    return jax.lax.dynamic_update_slice_in_dim(X, x, start, 0)


@functools.partial(jax.jit, static_argnames=("k", "eps"))
def _route(x, ln, router, r, *, k, eps):
    """Normed input, the k renormalized weights and experts of each row, and
    how far the k-th expert's probability lies above the next one's."""
    h = _rms(x, ln[r], eps)
    probs = jax.nn.softmax(jnp.dot(h, router[r].astype(F32), precision=HI), axis=-1)
    topw, topi = jax.lax.top_k(probs, k + 1)
    margin = topw[:, k - 1] - topw[:, k]
    topw, topi = topw[:, :k], topi[:, :k]
    return h, topw / jnp.sum(topw, axis=-1, keepdims=True), topi, margin


@functools.partial(jax.jit, static_argnames=("quant",))
def _expert(y, h, idx, gate, wg, wu, wd, r, e, *, quant):
    """y[idx] += gate * SwiGLU_e(h[idx]) for expert ``e`` of stacked layer
    ``r``; padding entries of ``idx`` point past the last row."""
    hp = jnp.take(h, idx, axis=0, mode="fill", fill_value=0)
    a = jnp.dot(hp, _w(wg[r, e], quant, 0), precision=HI)
    b = jnp.dot(hp, _w(wu[r, e], quant, 0), precision=HI)
    out = jnp.dot(jax.nn.silu(a) * b, _w(wd[r, e], quant, 0), precision=HI)
    return y.at[idx].add(out * gate[:, None], mode="drop")


@functools.partial(jax.jit, static_argnames=("quant",))
def _swiglu(h, wi, wg, wo, r, *, quant):
    a = jnp.dot(h, _w(wg[r], quant, 0), precision=HI)
    b = jnp.dot(h, _w(wi[r], quant, 0), precision=HI)
    return jnp.dot(jax.nn.silu(a) * b, _w(wo[r], quant, 0), precision=HI)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(X, rows, ln, head, *, eps, quant):
    return jnp.dot(_rms(X[rows], ln, eps), _w(head, quant, 0), precision=HI)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_in(X, ln, r, *, eps):
    return _rms(X, ln[r], eps)


def _bucket(n: int, least: int = 64) -> int:
    return max(least, 1 << (max(n, 1) - 1).bit_length())


def _layers(params):
    """(stacked layer params, index in the stack) in model order."""
    for g in params["groups"]:
        (p,) = g["layers"]
        for r in range(jax.tree.leaves(p)[0].shape[0]):
            yield p, r


def forward(params, cfg: dict, seqs: Sequence[np.ndarray], keep: Sequence[int],
            quant: str = "none", margins: bool = False):
    """Logits (float32, on the host) of each sequence at its last
    ``keep[i]`` positions: row j scores the token after position
    ``len(seq) - keep[i] + j``. With ``margins``, also the routing margin
    of the same positions: the least, over the MoE layers, of how far the
    k-th expert's router probability lies above the next one's (a near-tie
    that rounding can flip).

    The sequences lie in one buffer, each in a segment of the engine's
    ``max_seq`` rounded up to ``QBLOCK`` rows, and the buffer is padded to
    a power of two: every program sees the same few shapes whatever the
    lengths, so it compiles once and is found in the cache on later runs.
    Padding rows are routed to no expert and never read."""
    w = widths(cfg)
    lens = [len(s) for s in seqs]
    Sp = -(-max([cfg["engine"]["max_seq"], *lens]) // QBLOCK) * QBLOCK
    seg = [Sp] * len(seqs)
    offs = np.concatenate([[0], np.cumsum(seg)]).astype(int)
    Tp = _bucket(int(offs[-1]), QBLOCK)
    tokens = np.zeros(Tp, np.int32)
    valid = np.zeros(Tp, bool)
    for o, s in zip(offs, seqs):
        tokens[o:o + len(s)] = s
        valid[o:o + len(s)] = True
    X = params["embed"]["w"][jnp.asarray(tokens)].astype(F32)              # (Tp, d)
    least = np.full(Tp, np.inf, np.float32)
    for p, r in _layers(params):
        a = p["attn"]
        for o, n, sp in zip(offs, lens, seg):
            X = _attention(X, int(o), n, p["ln1"], a["wq"], a["wk"], a["wv"], a["wo"], r,
                           Sp=sp, theta=w["theta"], eps=w["eps"], quant=quant)
        if "moe" in p:
            m = p["moe"]
            h, topw, topi, margin = _route(X, p["ln2"], m["router"], r, k=w["k"], eps=w["eps"])
            least = np.minimum(least, np.asarray(margin))
            topi_h, topw_h = np.asarray(topi), np.asarray(topw)
            y = jnp.zeros_like(X)
            for e in range(w["E"]):
                tok, slot = np.nonzero((topi_h == e) & valid[:, None])
                if not len(tok):
                    continue
                B = _bucket(len(tok))
                idx = np.full(B, Tp, np.int32)
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
    out, near = [], []
    for o, n, k in zip(offs, lens, keep):
        near.append(least[o + n - k:o + n])
        B = _bucket(k)
        rows = np.full(B, o + n - 1, np.int32)
        rows[:k] = np.arange(o + n - k, o + n)
        lg = _logits(X, rows, params["final_norm"]["w"], params["lm_head"]["w"],
                     eps=w["eps"], quant=quant)
        out.append(np.asarray(lg)[:k])
    return (out, near) if margins else out
