"""MoE: strategy equivalence (capacity == tp_shardmap == ep_shardmap on a
mesh), dropless exactness, router properties (hypothesis), and the routed
experts reaching every layer of the scan as stacks with the layer's index."""
import dataclasses

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


ROUTED = ("wg", "wu", "wd")


@pytest.fixture(scope="module")
def moe_setup():
    """The second layer of a two-layer MoE group, as the layer scan hands it
    over: the routed experts stacked over both layers, the router its own."""
    cfg = tiny_config("mixtral-8x7b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    stacked = params["groups"][0]["layers"][0]["moe"]
    layer = 1
    p = {k: v if k in ROUTED else v[layer] for k, v in stacked.items()}
    h = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, cfg.d_model)),
                    jnp.float32)
    return cfg, p, layer, h


def test_strategies_agree_on_mesh(moe_setup):
    cfg, p, layer, h = moe_setup
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    outs = {}
    for strat in ["capacity", "tp_shardmap", "ep_shardmap"]:
        ctx = RunCtx(moe_strategy=strat, mesh=mesh if "shardmap" in strat else None)
        with mesh:
            y, aux, _ = moe_sublayer(p, h, cfg, ctx, layer)
        outs[strat] = np.asarray(y)
    np.testing.assert_allclose(outs["capacity"], outs["tp_shardmap"], atol=1e-5)
    np.testing.assert_allclose(outs["capacity"], outs["ep_shardmap"], atol=1e-5)


def test_dropless_weights_sum(moe_setup):
    """Dropless output is a convex combination of expert outputs — compare
    against a brute-force dense evaluation."""
    cfg, p, layer, h = moe_setup
    ctx = RunCtx(moe_strategy="dropless")
    y, aux, _ = moe_sublayer(p, h, cfg, ctx, layer)
    wg, wu, wd = (p[k][layer] for k in ROUTED)
    xf = h.reshape(-1, h.shape[-1])
    topw, topi, _ = router_topk(xf, p["router"], cfg.moe.top_k)
    dense = jnp.einsum("ecd,edf->ecf", xf[None].repeat(cfg.moe.num_experts, 0), wg)
    h1 = dense
    h2 = jnp.einsum("ecd,edf->ecf", xf[None].repeat(cfg.moe.num_experts, 0), wu)
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h1) * h2, wd)   # (E,T,d)
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


# ---------------------------------------------------------------- layer scan
def _split_groups(cfg, params):
    """The same model with each group of R repeats cut into R groups of one:
    every routed-expert stack then holds a single layer, read at index 0."""
    groups, gparams = [], []
    for g, gp in zip(cfg.layer_groups, params["groups"]):
        for r in range(g.repeats):
            groups.append(dataclasses.replace(g, repeats=1))
            gparams.append(jax.tree.map(lambda x, r=r: x[r:r + 1], gp))
    return (dataclasses.replace(cfg, layer_groups=tuple(groups)),
            {**params, "groups": gparams})


def _join_groups(cfg, cache):
    """Undo ``_split_groups`` on a cache: restack the one-repeat groups."""
    out, i = [], 0
    for g in cfg.layer_groups:
        parts = cache["groups"][i:i + g.repeats]
        out.append(jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts))
        i += g.repeats
    return {"groups": out}


def _serve(model, params, ctx):
    """A prefill chunk of 4 and 3 tokens on two rows, then a decode token each,
    through the paged pool: the logits of both calls and the pool after. Each
    call is one jitted program, as the engine runs it (op by op, XLA fuses
    the scan's body and the unrolled layers apart, and rounds them apart)."""
    cfg = model.cfg
    toks = np.random.default_rng(5).integers(0, cfg.vocab, (2, 5)).astype(np.int32)
    cache = model.init_cache(2, 32, jnp.float32, kind="paged", page_size=4, num_pages=16)
    pt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    slots = jnp.asarray([0, 1], jnp.int32)
    step = jax.jit(lambda p, t, c, st, nv, first: model.decode_chunk(
        p, t, c, st, nv, slots, first, ctx, pt))
    lg1, cache = step(params, jnp.asarray(toks[:, :4]), cache, jnp.zeros((2,), jnp.int32),
                      jnp.asarray([4, 3], jnp.int32), jnp.asarray([True, True]))
    lg2, cache = step(params, jnp.asarray(toks[:, 4:]), cache, jnp.asarray([4, 3], jnp.int32),
                      jnp.ones((2,), jnp.int32), jnp.asarray([False, False]))
    return [np.asarray(lg1), np.asarray(lg2)], cache


def _assert_trees(a, b, exact, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if exact:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol, rtol=atol)


@pytest.fixture(scope="module", params=["mixtral-8x7b", "deepseek-moe-16b", "deepseek-v2-lite"])
def served_moe(request):
    cfg = tiny_config(request.param)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    ctx = RunCtx(attn_backend="xla", moe_strategy="dropless")
    return cfg, model, params, ctx, _serve(model, params, ctx)


def test_decode_chunk_unrolled_equals_scan(served_moe):
    """The scanned and the unrolled layer loops hand the experts the same
    stacks and layer index: identical logits and pools."""
    cfg, model, params, ctx, (logits, cache) = served_moe
    logits_u, cache_u = _serve(model, params, dataclasses.replace(ctx, scan_layers=False))
    _assert_trees(logits, logits_u, exact=True)
    _assert_trees(cache, cache_u, exact=True)


def test_decode_chunk_capacity_equals_dropless(served_moe):
    """``moe_capacity`` slices the layer out of the stacks itself; at 8
    tokens a call no expert overflows its capacity, so it serves what the
    dropless path serves."""
    cfg, model, params, ctx, (logits, cache) = served_moe
    logits_c, cache_c = _serve(model, params, dataclasses.replace(ctx, moe_strategy="capacity"))
    _assert_trees(logits, logits_c, exact=False)
    _assert_trees(cache, cache_c, exact=False)


def test_decode_chunk_stack_of_one_equals_stack(served_moe):
    """A model whose groups each hold one layer reads every stack at index 0;
    cutting a two-layer MoE group so must not change what is served."""
    cfg, model, params, ctx, (logits, cache) = served_moe
    cfg1, params1 = _split_groups(cfg, params)
    logits_1, cache_1 = _serve(build_model(cfg1), params1, ctx)
    _assert_trees(logits, logits_1, exact=False)
    _assert_trees(cache, _join_groups(cfg, cache_1), exact=False)
