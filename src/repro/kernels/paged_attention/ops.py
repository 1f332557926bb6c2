"""Public paged attention ops (chunked prefill over K/V pools, the latent
sweep) with pallas/xla dispatch; a decode step is a chunk of one.

The pallas path is what the engine serves on a TPU; the xla path (gather via
page_table indexing) is what it serves elsewhere and the oracle the kernels
are tested against, in interpret mode on the CPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.backend import resolve
from repro.kernels.paged_attention import kernel as _kernel
from repro.kernels.paged_attention.ref import (chunked_prefill_reference,
                                               mla_paged_reference)


def chunked_prefill_attention(
    q, k_pages, v_pages, page_table, lengths, q_positions, *,
    scale: float | None = None, softcap: float = 0.0, window: int = 0,
    backend: str = "auto", interpret: bool | None = None,
):
    """Chunked paged prefill: q (B, C, H, D) at absolute q_positions (B, C)
    attends causally over the pool (the chunk's own KV included).

    The pallas kernel assumes the positions of a row are contiguous
    (``q_positions[b, i] == q_positions[b, 0] + i`` — true for every
    engine-issued chunk); callers with non-affine positions (e.g. a VLM
    patch-prefix row) must use the xla path.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    backend, interpret = resolve(backend, interpret)
    if backend == "xla":
        return chunked_prefill_reference(
            q, k_pages, v_pages, page_table, lengths, q_positions,
            scale=scale, softcap=softcap, window=window,
        )
    starts = q_positions[:, 0].astype(jnp.int32)
    return _kernel.chunked_prefill_pallas(
        q, k_pages, v_pages, page_table, lengths, starts,
        scale=scale, softcap=softcap, window=window, interpret=interpret,
    )


def mla_paged_attention(
    q, pool, page_table, lengths, q_positions, *, scale: float, dv: int,
    backend: str = "auto", interpret: bool | None = None,
):
    """Latent paged attention: absorbed queries q (B, C, H, Dk) at absolute
    q_positions (B, C) attend causally over the latent pool (the chunk's own
    rows included); the values are the pool's first ``dv`` columns. Row
    positions must be contiguous, as for ``chunked_prefill_attention``."""
    backend, interpret = resolve(backend, interpret)
    if backend == "xla":
        return mla_paged_reference(q, pool, page_table, lengths, q_positions,
                                   scale=scale, dv=dv)
    starts = q_positions[:, 0].astype(jnp.int32)
    return _kernel.mla_paged_pallas(q, pool, page_table, lengths, starts,
                                    scale=scale, dv=dv, interpret=interpret)
