"""Grouped expert matmul (megablox-style gmm) vs oracle, incl. hypothesis
sweep over ragged group sizes (empty groups, single-expert skew) and over
stacks of layers read in place."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st

from repro.kernels.moe_gmm import gmm, gmm_reference


SIZES = [[8, 8, 8, 8], [0, 32, 0, 1], [33], [1, 1, 1, 1, 29]]


def _run(rng, group_sizes, L=1, K=16, N=24, block_m=8, block_n=8):
    """Every layer of an (L, E, K, N) stack, read in place by the kernel and
    by the XLA path, against the oracle on that layer alone; with L > 1 each
    must equal, bit for bit, gmm on the layer alone (``w[l][None]``, layer 0):
    the kernel reads the same tiles in the same order."""
    gs = jnp.asarray(np.asarray(group_sizes, np.int32))
    M, E = int(gs.sum()), len(group_sizes)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((L, E, K, N)), jnp.float32)
    kw = dict(block_m=block_m, block_n=block_n)
    for layer in range(L):
        alone = w[layer][None]
        ref = gmm_reference(x, alone, gs, 0)
        out = gmm(x, w, gs, layer, backend="pallas", interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)
        if L > 1:
            np.testing.assert_array_equal(
                np.asarray(out),
                np.asarray(gmm(x, alone, gs, 0, backend="pallas", interpret=True, **kw)))
            np.testing.assert_array_equal(np.asarray(gmm(x, w, gs, layer, backend="xla")),
                                          np.asarray(ref))


# a group of size 0, groups that are not whole tiles (block_m 8), and the
# padding tiles past the last group, which map to expert E-1; each on one
# layer (``w[None]``, layer 0) and on a stack of three
@pytest.mark.parametrize("sizes,L", [pytest.param(s, 1, id=f"sizes{i}") for i, s in enumerate(SIZES)]
                         + [pytest.param(s, 3, id=f"stacked{i}") for i, s in enumerate(SIZES)])
def test_gmm_fixed(rng, sizes, L):
    _run(rng, sizes, L)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 20), min_size=1, max_size=6).filter(lambda s: sum(s) > 0),
       st.integers(1, 3))
def test_gmm_hypothesis(sizes, L):
    _run(np.random.default_rng(sum(sizes)), sizes, L)


def test_gmm_bf16(rng):
    gs = jnp.asarray([5, 11], jnp.int32)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), jnp.bfloat16)
    ref = gmm_reference(x, w, gs, 0)
    out = gmm(x, w, gs, 0, backend="pallas", interpret=True, block_m=8, block_n=8)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=5e-2, rtol=5e-2)
