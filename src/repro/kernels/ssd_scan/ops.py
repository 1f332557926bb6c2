"""Public SSD chunk-scan op: reshapes/chunk-prep + pallas/xla dispatch.
Same signature as models.mamba.ssd_chunked (minus init_state: the kernel owns
the state in VMEM; chunked-prefill continuation uses the jnp path)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.backend import resolve
from repro.kernels.ssd_scan import kernel as _kernel
from repro.kernels.ssd_scan.ref import ssd_reference


def ssd_scan(x, dt, A, B_, C, chunk: int, *, backend: str = "auto",
             interpret: bool | None = None):
    """x (B,L,H,P); dt (B,L,H) post-softplus; A (H,) negative; B_/C (B,L,H,N).
    Returns y (B,L,H,P) — matches ssd_reference(...)[0]."""
    backend, interpret = resolve(backend, interpret)
    if backend == "xla":
        return ssd_reference(x, dt, A, B_, C, chunk)[0]

    Bb, L, H, P = x.shape
    N = B_.shape[-1]
    pad = (-L) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    nc = Lp // chunk

    def to_bh(a, d):   # (B,L,H,d) -> (B*H, nc, Q, d)
        return a.transpose(0, 2, 1, 3).reshape(Bb * H, nc, chunk, d)

    dtc = dt.transpose(0, 2, 1).reshape(Bb * H, nc, chunk, 1).astype(jnp.float32)
    dA = dtc * A.astype(jnp.float32)[None, :, None].repeat(Bb, 0).reshape(Bb * H, 1, 1, 1)
    cum = jnp.cumsum(dA, axis=2)
    y = _kernel.ssd_scan_pallas(
        to_bh(x, P), dtc, cum, to_bh(B_, N), to_bh(C, N),
        chunk=chunk, interpret=interpret)
    y = y.reshape(Bb, H, Lp, P).transpose(0, 2, 1, 3)
    return y[:, :L]
