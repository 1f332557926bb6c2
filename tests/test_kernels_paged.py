"""The served paged-attention kernel as a decode step (a one-token chunk,
``chunked_prefill_attention`` in the Pallas interpreter) vs the decode
oracle: ragged lengths, GQA groups, sliding window, page-size sweep, bf16,
softcap."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import chunked_prefill_attention, paged_attention_reference


def _decode(q, kp, vp, pt, lengths, **kw):
    """q (B, H, D) as a width-1 chunk at position lengths - 1: (B, H, D)."""
    out = chunked_prefill_attention(q[:, None], kp, vp, pt, lengths, (lengths - 1)[:, None],
                                    backend="pallas", interpret=True, **kw)
    return out[:, 0]


@pytest.mark.parametrize("H,Hkv,D", [(8, 2, 16), (4, 4, 32), (8, 1, 64)])
@pytest.mark.parametrize("page_size", [4, 8])
@pytest.mark.parametrize("window", [0, 9])
def test_paged_vs_reference(rng, H, Hkv, D, page_size, window):
    B, P, maxp = 3, 24, 5
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, Hkv, page_size, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, Hkv, page_size, D)), jnp.float32)
    pt = jnp.asarray(rng.integers(1, P, (B, maxp)), jnp.int32)
    lengths = jnp.asarray([1, page_size * 2 + 3, maxp * page_size], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, pt, lengths, window=window)
    out = _decode(q, kp, vp, pt, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_paged_bf16(rng):
    B, H, Hkv, D, P, ps, maxp = 2, 4, 2, 32, 16, 8, 4
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((P, Hkv, ps, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((P, Hkv, ps, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.integers(0, P, (B, maxp)), jnp.int32)
    lengths = jnp.asarray([7, 30], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, pt, lengths)
    out = _decode(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2)


def test_softcap(rng):
    B, H, Hkv, D, P, ps, maxp = 2, 4, 2, 16, 8, 4, 3
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, Hkv, ps, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, Hkv, ps, D)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, P, (B, maxp)), jnp.int32)
    lengths = jnp.asarray([5, 12], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, pt, lengths, softcap=30.0)
    out = _decode(q, kp, vp, pt, lengths, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
