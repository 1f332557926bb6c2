"""Oracle for the grouped (ragged) expert matmul.

x:           (M, K)  rows sorted by expert id
w:           (E, K, N)
group_sizes: (E,)    sum == M
out[m] = x[m] @ w[expert_of(m)]
"""
from __future__ import annotations

import jax.numpy as jnp


def expert_of_rows(group_sizes, M):
    """(M,) expert id per row from group sizes (rows sorted by expert)."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(M), side="right")


def gmm_reference(x, w, group_sizes):
    M, K = x.shape
    E, _, N = w.shape
    eid = expert_of_rows(group_sizes, M)
    # O(E * M * K * N) dense oracle: compute every expert for every row, select.
    all_out = jnp.einsum("mk,ekn->emn", x, w, preferred_element_type=jnp.float32)
    out = jnp.take_along_axis(all_out, eid[None, :, None], axis=0)[0]
    return out.astype(x.dtype)
