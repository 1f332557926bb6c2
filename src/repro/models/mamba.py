"""Mamba2 layer via SSD (state-space duality, arXiv:2405.21060).

Chunked algorithm (the full sequence, and serving chunks of more than one
token): within-chunk quadratic ("attention-like") term + cross-chunk state
recurrence (lax.scan over chunks). A one-token chunk (decode) is an O(1)
state update.

Cache per layer, one slot per engine slot: {"state": (slots, H, P, N) f32,
"conv": (slots, conv_dim, d_conv-1)}; the full sequence runs with none.
All decays are exp(<=0) — numerically bounded by construction.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import rmsnorm


def _split_proj(zxbcdt, cfg: ModelConfig):
    d_in, H = cfg.d_inner, cfg.ssm_heads
    GN = cfg.ssm.n_groups * cfg.ssm.d_state
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in : 2 * d_in + 2 * GN]
    dt = zxbcdt[..., 2 * d_in + 2 * GN :]
    return z, xbc, dt


def _conv_full(xbc, conv_w, conv_b):
    """Causal depthwise conv over sequence. xbc (B,S,C); conv_w (C, K)."""
    B, S, C = xbc.shape
    K = conv_w.shape[-1]
    lhs = xbc.transpose(0, 2, 1)                          # (B, C, S)
    rhs = conv_w[:, None, :]                              # (C, 1, K)
    out = jax.lax.conv_general_dilated(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32),
        window_strides=(1,), padding=[(K - 1, 0)], feature_group_count=C,
    )
    out = out.transpose(0, 2, 1) + conv_b[None, None, :]
    return jax.nn.silu(out).astype(xbc.dtype)


def _conv_step(xbc_new, conv_state, conv_w, conv_b):
    """xbc_new (B,1,C); conv_state (B,C,K-1). Returns (out (B,1,C), new_state)."""
    window = jnp.concatenate([conv_state, xbc_new.transpose(0, 2, 1)], axis=-1)  # (B,C,K)
    out = jnp.sum(window.astype(jnp.float32) * conv_w[None].astype(jnp.float32), axis=-1)
    out = jax.nn.silu(out + conv_b[None]).astype(xbc_new.dtype)
    return out[:, None, :], window[..., 1:]


def _conv_carry(xbc, conv_state, conv_w, conv_b):
    """Causal conv continuing from a carried tail. xbc (B,S,C); conv_state
    (B,C,K-1) holds the K-1 inputs preceding the chunk (zeros at sequence
    start). Returns (out (B,S,C), window (B, K-1+S, C)) — the window is
    reused by the caller to slice the next carry at a ragged boundary."""
    B, S, C = xbc.shape
    K = conv_w.shape[-1]
    window = jnp.concatenate([conv_state.transpose(0, 2, 1).astype(xbc.dtype), xbc],
                             axis=1)                       # (B, K-1+S, C)
    lhs = window.transpose(0, 2, 1)                        # (B, C, K-1+S)
    rhs = conv_w[:, None, :]                               # (C, 1, K)
    out = jax.lax.conv_general_dilated(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32),
        window_strides=(1,), padding="VALID", feature_group_count=C,
    )
    out = out.transpose(0, 2, 1) + conv_b[None, None, :]
    return jax.nn.silu(out).astype(xbc.dtype), window


def ssd_chunked(x, dt, A, B_, C, chunk: int, init_state=None):
    """x (B,L,H,P); dt (B,L,H) post-softplus; A (H,) negative; B_/C (B,L,H,N).
    Returns (y (B,L,H,P), final_state (B,H,P,N))."""
    Bb, L, H, Pd = x.shape
    N = B_.shape[-1]
    pad = (-L) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    nc, Q = Lp // chunk, chunk

    f32 = jnp.float32
    xc = x.reshape(Bb, nc, Q, H, Pd).astype(f32)
    dtc = dt.reshape(Bb, nc, Q, H).astype(f32)
    Bc = B_.reshape(Bb, nc, Q, H, N).astype(f32)
    Cc = C.reshape(Bb, nc, Q, H, N).astype(f32)

    dA = dtc * A[None, None, None, :]                     # (B,nc,Q,H) <= 0
    cum = jnp.cumsum(dA, axis=2)                          # inclusive

    # within-chunk (quadratic) term
    CB = jnp.einsum("bcihn,bcjhn->bchij", Cc, Bc)         # (B,nc,H,Q,Q)
    decay = jnp.exp(cum.transpose(0, 1, 3, 2)[..., :, None] - cum.transpose(0, 1, 3, 2)[..., None, :])
    ii = jnp.arange(Q)
    causal = (ii[:, None] >= ii[None, :])[None, None, None]
    M = jnp.where(causal, CB * decay, 0.0) * dtc.transpose(0, 1, 3, 2)[..., None, :]
    y_diag = jnp.einsum("bchij,bcjhp->bcihp", M, xc)

    # per-chunk input states and cross-chunk recurrence
    decay_out = jnp.exp(cum[:, :, -1:, :] - cum)          # (B,nc,Q,H)
    S_c = jnp.einsum("bcjh,bcjhn,bcjhp->bchpn", decay_out * dtc, Bc, xc)  # (B,nc,H,P,N)
    T_c = jnp.exp(cum[:, :, -1, :])                       # (B,nc,H)

    s0 = jnp.zeros((Bb, H, Pd, N), f32) if init_state is None else init_state.astype(f32)

    def chunk_step(s, inputs):
        t_c, s_c = inputs                                 # (B,H), (B,H,P,N)
        s_new = s * t_c[..., None, None] + s_c
        return s_new, s                                   # emit state BEFORE this chunk

    T_s = T_c.transpose(1, 0, 2)                          # (nc,B,H)
    S_s = S_c.transpose(1, 0, 2, 3, 4)                    # (nc,B,H,P,N)
    final_state, prev_states = jax.lax.scan(chunk_step, s0, (T_s, S_s))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)    # (B,nc,H,P,N)

    decay_in = jnp.exp(cum)                               # (B,nc,Q,H)
    y_off = jnp.einsum("bcihn,bchpn->bcihp", Cc, prev_states) * decay_in[..., None]

    y = (y_diag + y_off).reshape(Bb, Lp, H, Pd)[:, :L]
    return y, final_state


def _ssm_decode_update(xbc_c, dt1, A, p, state, cfg: ModelConfig):
    """One-token SSD state update. xbc_c (B,1,conv_dim) post-conv; dt1 (B,H);
    state (B,H,P,N) f32. Returns (y (B,1,d_inner) f32, new_state f32)."""
    d_in, H, Pd = cfg.d_inner, cfg.ssm_heads, cfg.ssm.head_dim
    G, N = cfg.ssm.n_groups, cfg.ssm.d_state
    B = xbc_c.shape[0]
    xh = xbc_c[:, 0, :d_in].reshape(B, H, Pd).astype(jnp.float32)
    Bm = xbc_c[:, 0, d_in : d_in + G * N].reshape(B, G, N).astype(jnp.float32)
    Cm = xbc_c[:, 0, d_in + G * N :].reshape(B, G, N).astype(jnp.float32)
    Bm = jnp.repeat(Bm, H // G, axis=1)                   # (B,H,N)
    Cm = jnp.repeat(Cm, H // G, axis=1)
    dA = jnp.exp(dt1 * A[None, :])                        # (B,H)
    state = state * dA[..., None, None] + jnp.einsum("bh,bhn,bhp->bhpn", dt1, Bm, xh)
    y = jnp.einsum("bhn,bhpn->bhp", Cm, state)            # (B,H,P)
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xh
    return y.reshape(B, 1, d_in), state


def mamba_sublayer(
    p: Dict[str, Any],
    h,                      # normed (B, S, d)
    cfg: ModelConfig,
    cache: Optional[Dict[str, Any]] = None,
    chunk: Optional[Dict[str, Any]] = None,
) -> Tuple[Any, Optional[Dict[str, Any]]]:
    """Returns (out (B,S,d), new_cache): with the chunk pack, a chunk over the
    slot-pooled state; without, the full sequence from zero state (new_cache
    None)."""
    ssm = cfg.ssm
    d_in, H, Pd = cfg.d_inner, cfg.ssm_heads, ssm.head_dim
    G, N, K = ssm.n_groups, ssm.d_state, ssm.d_conv
    B, S, _ = h.shape

    zxbcdt = jnp.einsum("bsd,dk->bsk", h, p["in_proj"])
    z, xbc, dt_raw = _split_proj(zxbcdt, cfg)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))          # (H,)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    if chunk is not None:
        # serving chunk over the slot-pooled cache: rows map to engine slots,
        # first chunks start from zero state, ragged tails are masked via dt
        # (dt == 0 => exp(dt*A) == 1 and zero input: the state is untouched).
        slots, nvalid, first = chunk["slots"], chunk["nvalid"], chunk["first"]
        row_valid = nvalid > 0
        s_orig = cache["state"][slots]
        c_orig = cache["conv"][slots]
        s0 = jnp.where(first[:, None, None, None], 0.0, s_orig.astype(jnp.float32))
        c0 = jnp.where(first[:, None, None], jnp.zeros_like(c_orig), c_orig)
        if S == 1:                                        # decode: O(1) update
            xbc_c, conv_new = _conv_step(xbc, c0, p["conv_w"], p["conv_b"])
            y, state_new = _ssm_decode_update(xbc_c, dt[:, 0], A, p, s0, cfg)
        else:
            xbc_c, window = _conv_carry(xbc, c0, p["conv_w"], p["conv_b"])
            xh = xbc_c[..., :d_in].reshape(B, S, H, Pd)
            Bm = xbc_c[..., d_in : d_in + G * N].reshape(B, S, G, N)
            Cm = xbc_c[..., d_in + G * N :].reshape(B, S, G, N)
            Bm = jnp.repeat(Bm, H // G, axis=2)
            Cm = jnp.repeat(Cm, H // G, axis=2)
            dtm = jnp.where(jnp.arange(S)[None, :, None] < nvalid[:, None, None],
                            dt, 0.0)
            y, state_new = ssd_chunked(xh, dtm, A, Bm, Cm, ssm.chunk_size,
                                       init_state=s0)
            y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh.astype(jnp.float32)
            y = y.reshape(B, S, d_in)
            # next carry: the K-1 inputs preceding each row's ragged end
            idx = nvalid[:, None] + jnp.arange(K - 1)[None]
            conv_new = jnp.take_along_axis(window, idx[..., None], axis=1
                                           ).transpose(0, 2, 1)
        new_cache = {
            "state": cache["state"].at[slots].set(
                jnp.where(row_valid[:, None, None, None],
                          state_new.astype(cache["state"].dtype), s_orig)),
            "conv": cache["conv"].at[slots].set(
                jnp.where(row_valid[:, None, None],
                          conv_new.astype(cache["conv"].dtype), c_orig)),
        }
    else:
        xbc_c = _conv_full(xbc, p["conv_w"], p["conv_b"])
        xh = xbc_c[..., :d_in].reshape(B, S, H, Pd)
        Bm = xbc_c[..., d_in : d_in + G * N].reshape(B, S, G, N)
        Cm = xbc_c[..., d_in + G * N :].reshape(B, S, G, N)
        Bm = jnp.repeat(Bm, H // G, axis=2)
        Cm = jnp.repeat(Cm, H // G, axis=2)
        y, _ = ssd_chunked(xh, dt, A, Bm, Cm, ssm.chunk_size)
        y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh.astype(jnp.float32)
        y = y.reshape(B, S, d_in)
        new_cache = None

    # gated RMSNorm + out projection
    y = rmsnorm((y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype), p["norm"], cfg.rms_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"])
    return out, new_cache
