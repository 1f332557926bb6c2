"""MoE: strategy equivalence (capacity == tp_shardmap == ep_shardmap on a
mesh), dropless exactness, router properties (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st

from repro.configs import tiny_config
from repro.models import RunCtx, build_model
from repro.models.moe import (capacity_combine, capacity_dispatch, moe_sublayer,
                              router_topk)


@pytest.fixture(scope="module")
def moe_setup():
    cfg = tiny_config("mixtral-8x7b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    p = jax.tree.map(lambda x: x[0], params["groups"][0]["layers"][0]["moe"])
    h = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, cfg.d_model)),
                    jnp.float32)
    return cfg, p, h


def test_strategies_agree_on_mesh(moe_setup):
    cfg, p, h = moe_setup
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    outs = {}
    for strat in ["capacity", "tp_shardmap", "ep_shardmap"]:
        ctx = RunCtx(moe_strategy=strat, mesh=mesh if "shardmap" in strat else None)
        with mesh:
            y, aux, _ = moe_sublayer(p, h, cfg, ctx)
        outs[strat] = np.asarray(y)
    np.testing.assert_allclose(outs["capacity"], outs["tp_shardmap"], atol=1e-5)
    np.testing.assert_allclose(outs["capacity"], outs["ep_shardmap"], atol=1e-5)


def test_dropless_weights_sum(moe_setup):
    """Dropless output is a convex combination of expert outputs — compare
    against a brute-force dense evaluation."""
    cfg, p, h = moe_setup
    ctx = RunCtx(moe_strategy="dropless")
    y, aux, _ = moe_sublayer(p, h, cfg, ctx)
    xf = h.reshape(-1, h.shape[-1])
    topw, topi, _ = router_topk(xf, p["router"], cfg.moe.top_k)
    dense = jnp.einsum("ecd,edf->ecf", xf[None].repeat(cfg.moe.num_experts, 0), p["wg"])
    h1 = dense
    h2 = jnp.einsum("ecd,edf->ecf", xf[None].repeat(cfg.moe.num_experts, 0), p["wu"])
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h1) * h2, p["wd"])   # (E,T,d)
    expect = jnp.zeros_like(xf)
    for kk in range(cfg.moe.top_k):
        expect = expect + topw[:, kk, None] * jnp.take_along_axis(
            ye, topi[:, kk][None, :, None], axis=0)[0]
    np.testing.assert_allclose(np.asarray(y.reshape(-1, h.shape[-1])),
                               np.asarray(expect), atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 64), st.integers(2, 8), st.integers(1, 4))
def test_router_topk_properties(T, E, K):
    K = min(K, E)
    r = np.random.default_rng(T * E + K)
    xf = jnp.asarray(r.standard_normal((T, 8)), jnp.float32)
    w = jnp.asarray(r.standard_normal((8, E)), jnp.float32)
    topw, topi, aux = router_topk(xf, w, K)
    assert topw.shape == (T, K) and topi.shape == (T, K)
    np.testing.assert_allclose(np.asarray(topw.sum(-1)), 1.0, atol=1e-5)
    assert bool(jnp.all(topw >= 0))
    assert bool(jnp.all((topi >= 0) & (topi < E)))
    # distinct experts per token
    for row in np.asarray(topi):
        assert len(set(row.tolist())) == K
    # E * sum f*P ~= 1 at uniform routing, rises with imbalance; the exact
    # >=1 bound only holds for top-1, so assert the sane range.
    assert 0.9 <= float(aux) < E + 1e-5


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 40), st.integers(2, 6), st.integers(1, 3), st.integers(2, 16))
def test_capacity_dispatch_combine_identity(T, E, K, cap):
    """With identity expert fn, dispatch+combine returns sum_k w_k * x for
    tokens whose slots fit; dropped slots contribute 0."""
    K = min(K, E)
    r = np.random.default_rng(T + E + K + cap)
    xf = jnp.asarray(r.standard_normal((T, 4)), jnp.float32)
    topi = jnp.asarray(r.integers(0, E, (T, K)), jnp.int32)
    topw = jnp.ones((T, K), jnp.float32) / K
    ebuf, info = capacity_dispatch(xf, topi, E, cap)
    y = capacity_combine(ebuf, info, topw)
    keep = np.asarray(info[2]).reshape(T, K)
    expect = (np.asarray(xf)[:, None, :] * keep[:, :, None]).sum(1) / K
    np.testing.assert_allclose(np.asarray(y), expect, atol=1e-5)
