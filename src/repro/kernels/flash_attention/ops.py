"""Public attention op. Dispatch:

- ``backend="pallas"``   : the Pallas TPU kernel (interpret=True on CPU tests).
- ``backend="xla"``      : chunked online-softmax in pure jnp (double scan) —
                           identical math to the kernel, memory-bounded, lowers
                           on every backend. This is what the models trace for
                           the multi-pod dry-run, so the compiled HLO has
                           flash-style memory behaviour (no S x S buffer).
- ``backend="auto"``     : pallas on TPU else xla.

All paths are numerically validated against ``ref.mha_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve
from repro.kernels.flash_attention import kernel as _kernel


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(
    q,  # (B, Sq, H, D)
    k,  # (B, Skv, Hkv, D)
    v,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    scale: float | None = None,
    block_q: int = 512,
    block_kv: int = 1024,
    backend: str = "auto",
    interpret: bool | None = None,
    unroll: bool = False,
):
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    if scale is None:
        scale = D ** -0.5
    backend, interpret = resolve(backend, interpret)

    if backend == "xla":
        return mha_chunked(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, scale=scale,
            block_q=min(block_q, Sq), block_kv=min(block_kv, Skv),
            unroll=unroll,
        )

    block_q = min(block_q, max(Sq, 8))
    block_kv = min(block_kv, max(Skv, 128))
    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_kv)
    vp = _pad_to(v, 1, block_kv)
    group = H // Hkv
    q3 = qp.transpose(0, 2, 1, 3).reshape(B * H, qp.shape[1], D)
    k3 = kp.transpose(0, 2, 1, 3).reshape(B * Hkv, kp.shape[1], D)
    v3 = vp.transpose(0, 2, 1, 3).reshape(B * Hkv, vp.shape[1], D)
    o3 = _kernel.flash_attention_bhsd(
        q3, k3, v3, kv_len=Skv, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, scale=scale, block_q=block_q, block_kv=block_kv,
        group=group, interpret=interpret,
    )
    o = o3.reshape(B, H, qp.shape[1], D).transpose(0, 2, 1, 3)
    return o[:, :Sq]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_offset", "scale",
                     "block_q", "block_kv", "unroll"),
)
def mha_chunked(
    q, k, v, *, causal=True, window=0, softcap=0.0, q_offset=0,
    scale=None, block_q=1024, block_kv=1024, unroll=False,
):
    """Flash attention in pure jnp: lax.map over q blocks, lax.scan over kv
    chunks with online-softmax carry. Peak temp = (B, H, block_q, block_kv).
    ``unroll=True`` replaces the loops with Python loops so XLA cost_analysis
    sees every tile (roofline cost lowering)."""
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    group = H // Hkv
    if scale is None:
        scale = D ** -0.5

    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_kv)
    vp = _pad_to(v, 1, block_kv)
    Sqp, Skvp = qp.shape[1], kp.shape[1]
    nq, nkv = Sqp // block_q, Skvp // block_kv

    # (nq, B, bq, H, D) / (nkv, B, bkv, Hkv, D)
    qb = qp.reshape(B, nq, block_q, H, D).transpose(1, 0, 2, 3, 4)
    kb = kp.reshape(B, nkv, block_kv, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nkv, block_kv, Hkv, D).transpose(1, 0, 2, 3, 4)

    def q_block(args):
        qi, qblk = args  # qblk (B, bq, H, D)
        q_pos = qi * block_q + jnp.arange(block_q)[:, None] + q_offset  # (bq,1)

        def kv_step(carry, xs):
            m, l, acc = carry
            ki, kc, vc = xs  # (B, bkv, Hkv, D)
            k_pos = ki * block_kv + jnp.arange(block_kv)[None, :]  # (1,bkv)
            kc = jnp.repeat(kc, group, axis=2)
            vc = jnp.repeat(vc, group, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kc, preferred_element_type=jnp.float32) * scale
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            mask = k_pos < Skv
            if causal:
                mask = mask & (k_pos <= q_pos)
            if window > 0:
                mask = mask & (k_pos > q_pos - window)
            s = jnp.where(mask[None, None], s, _kernel.NEG_INF)
            m_cur = jnp.max(s, axis=-1)                     # (B,H,bq)
            m_new = jnp.maximum(m, m_cur)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask[None, None], p, 0.0)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p, vc.astype(jnp.float32))
            acc_new = acc * alpha[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, block_q), _kernel.NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, block_q), jnp.float32)
        a0 = jnp.zeros((B, H, block_q, D), jnp.float32)
        if unroll:
            carry = (m0, l0, a0)
            for j in range(nkv):
                carry, _ = kv_step(carry, (jnp.asarray(j), kb[j], vb[j]))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (jnp.arange(nkv), kb, vb))
        out = jnp.where(l[..., None] > 0, acc / jnp.maximum(l[..., None], 1e-30), 0.0)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,bq,H,D)

    if unroll:
        outs = jnp.stack([q_block((jnp.asarray(i), qb[i])) for i in range(nq)])
    else:
        outs = jax.lax.map(q_block, (jnp.arange(nq), qb))  # (nq,B,bq,H,D)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sqp, H, D)
    return out[:, :Sq]
