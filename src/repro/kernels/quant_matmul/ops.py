"""w8a16 matmul op with pallas/xla dispatch + quantize helper."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.backend import resolve
from repro.kernels.quant_matmul import kernel as _kernel
from repro.kernels.quant_matmul.ref import w8a16_matmul_reference


def quantize_int8(w, axis: int = 0):
    """Per-output-channel symmetric int8 quantization of a (K, N) weight.
    Returns (w_q int8, scale f32 per column)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return w_q, scale.reshape(-1).astype(jnp.float32)


def w8a16_matmul(x, w_q, scale, *, backend: str = "auto", interpret: bool | None = None, **blocks):
    backend, interpret = resolve(backend, interpret)
    if backend == "xla":
        return w8a16_matmul_reference(x, w_q, scale)
    return _kernel.w8a16_matmul_pallas(x, w_q, scale, interpret=interpret, **blocks)
