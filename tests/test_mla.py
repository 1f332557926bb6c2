"""Multi-head latent attention (DeepSeek-V2): YaRN's published numbers, the
latent Pallas sweep against its oracle, the absorbed form against the
expanded one, prefill then decode through the latent pool against the full
forward pass, the prefix cache and copy-on-write over latent pages, the
router's ``norm_topk``, and what a cached token costs."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import LayerGroup, MLAConfig, ModelConfig, MoEConfig, YarnConfig
from repro.core.engine import EngineConfig, InferenceEngine, kv_bytes_per_token
from repro.core.metrics import Request
from repro.kernels.paged_attention import kernel, mla_paged_attention, mla_paged_reference
from repro.models import RunCtx, build_model
from repro.models.common import yarn_correction_range, yarn_freqs, yarn_mscale
from repro.models.mla import absorbed_query, expanded_attention, softmax_scale
from repro.models.moe import router_topk
from repro.models.transformer import LM

XLA = RunCtx(attn_backend="xla", moe_strategy="dropless")
PALLAS = RunCtx(attn_backend="pallas", interpret=True, moe_strategy="dropless")
# f32 logits of the served path against the full forward pass: both compute
# in f32 and differ only in how sums are ordered (the absorbed form
# reassociates q·Wuk·c), which moves a logit by ~1e-6 at these widths; a
# bf16 latent pool moves it by ~1e-2 and must fail this
F32_ATOL = 1e-4


def tiny_mla(norm_topk: bool = False) -> ModelConfig:
    """The latent MoE decoder at tiny widths; YaRN over an original context
    of 64, so that its ramp bites at short lengths."""
    return ModelConfig(
        name="mla-tiny", family="moe", n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=32, vocab=256, dense_d_ff=96,
        moe=MoEConfig(num_experts=8, top_k=2, num_shared_experts=2, d_expert=32,
                      norm_topk=norm_topk),
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
                      v_head_dim=16),
        rope_yarn=YarnConfig(factor=40.0, original_max_position_embeddings=64),
        layer_groups=(LayerGroup("A", 1, moe_mask="0"), LayerGroup("A", 2, moe_mask="1")))


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_mla()
    model = build_model(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


# ---------------------------------------------------------------- YaRN
def test_yarn_matches_the_published_hand_values():
    cfg = get_config("deepseek-v2-lite")
    y = cfg.rope_yarn
    assert yarn_correction_range(y, 64, cfg.rope_theta) == (10, 23)
    assert yarn_mscale(y.factor, y.mscale) == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert yarn_mscale(y.factor, y.mscale) == pytest.approx(1.26080, abs=1e-5)
    assert softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    f = yarn_freqs(y, 64, cfg.rope_theta)
    base = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)       # ramp 0 up to low
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)   # ramp 1 from high
    t = (16 - 10) / 13                                              # a pair on the ramp
    assert f[16] == pytest.approx(base[16] * (1 - t) + base[16] / 40 * t, rel=1e-6)


# ---------------------------------------------------------------- kernel
def _bruteforce(q, pool, pt, starts, lengths, scale, dv):
    """Per query token and head, in numpy: softmax over the positions it may
    see, scored over every column, summing the first ``dv``."""
    B, C, H, _ = q.shape
    kv = pool[pt].reshape(B, -1, pool.shape[-1])
    pos = np.arange(kv.shape[1])
    out = np.zeros((B, C, H, dv), np.float32)
    for b in range(B):
        for i in range(C):
            m = (pos < lengths[b]) & (pos <= starts[b] + i)
            if not m.any():
                continue
            for h in range(H):
                s = np.where(m, kv[b] @ q[b, i, h] * scale, -np.inf)
                w = np.exp(s - s.max())
                out[b, i, h] = (w / w.sum()) @ kv[b, :, :dv]
    return out


# rows by resident length: a padding row, one page, one block of pages,
# one past it, and the whole table (not a whole number of blocks); each
# row's chunk ends at its length, as the engine issues it
LENGTHS = ("zero", "page", "block", "block+1", "table")


@pytest.mark.parametrize("C,row_blocks,lengths", [
    (1, 1, LENGTHS),
    (8, 1, LENGTHS),
    (8, 4, LENGTHS),
    (1, 1, ("page", "zero", "block+1")),
], ids=["C1", "C8", "C8-row-blocks", "C1-padding-between-rows"])
def test_mla_kernel_matches_bruteforce(C, row_blocks, lengths, monkeypatch):
    rng = np.random.default_rng(1)
    H, Dk, dv, ps, maxp = 4, 48, 32, 16, 20
    ppb, cb = kernel.mla_block_sizes(ps, maxp, C, H, Dk, dv, 4, 4)
    assert (ppb, cb) == (16, C) and maxp % ppb
    if row_blocks > 1:            # a budget that holds fewer query tokens a step
        monkeypatch.setattr(kernel, "VMEM_BUDGET", kernel._mla_vmem_bytes(
            ppb, C // row_blocks * H, ps, Dk, dv, 4, 4))
        assert kernel.mla_block_sizes(ps, maxp, C, H, Dk, dv, 4, 4) == (ppb, C // row_blocks)
        jax.clear_caches()
    at = {"zero": 0, "page": ps, "block": ppb * ps, "block+1": ppb * ps + 1,
          "table": maxp * ps}
    B = len(lengths)
    lens = np.array([at[n] for n in lengths])
    nvalid = np.minimum(lens, C)
    starts = lens - nvalid
    # pages scattered over the pool; entries past a row's pages are out of
    # range, so that a read of one would fail
    pt = np.full((B, maxp), -1)
    pages = rng.permutation(np.arange(1, B * maxp + 1))
    for b in range(B):
        used = -(-lens[b] // ps)
        pt[b, :used], pages = pages[:used], pages[used:]
    pool = rng.standard_normal((B * maxp + 1, ps, Dk)).astype(np.float32)
    q = rng.standard_normal((B, C, H, Dk)).astype(np.float32)
    scale = Dk ** -0.5
    oracle = _bruteforce(q, pool, np.maximum(pt, 0), starts, lens, scale, dv)
    args = (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(pt, jnp.int32),
            jnp.asarray(lens, jnp.int32), jnp.asarray(starts[:, None] + np.arange(C), jnp.int32))
    ref = mla_paged_reference(*args, scale=scale, dv=dv)
    pal = mla_paged_attention(*args, scale=scale, dv=dv, backend="pallas", interpret=True)
    if row_blocks > 1:
        jax.clear_caches()
    for b in range(B):
        n = nvalid[b]
        # f32 throughout; sums ordered differently
        np.testing.assert_allclose(np.asarray(ref)[b, :n], oracle[b, :n], atol=1e-5)
        np.testing.assert_allclose(np.asarray(pal)[b, :n], oracle[b, :n], atol=1e-5)
    assert not np.asarray(pal)[lens == 0].any()          # a padding row writes zeros


# ---------------------------------------------------------------- the layer
def test_absorbed_form_equals_expanded_form(setup):
    """In f32 the absorbed form (scores against the cached latent rows,
    o = (P·c)·Wuv) gives the expanded form's output."""
    cfg, _, params = setup
    p = jax.tree.map(lambda x: x[0], params["groups"][1]["layers"][0]["attn"])
    rng = np.random.default_rng(2)
    S, H, m = 24, cfg.n_heads, cfg.mla
    q_nope = jnp.asarray(rng.standard_normal((1, S, H, m.qk_nope_head_dim)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((1, S, H, m.qk_rope_head_dim)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((1, S, m.kv_lora_rank)), jnp.float32)
    k_pe = jnp.asarray(rng.standard_normal((1, S, m.qk_rope_head_dim)), jnp.float32)
    scale = softmax_scale(cfg)
    expanded = expanded_attention(q_nope, q_pe, c, k_pe, p, scale)
    pool = jnp.pad(jnp.concatenate([c, k_pe], -1), ((0, 0), (0, 0), (0, 80)))
    pool = pool.reshape(S // 8, 8, 128)
    o_lat = mla_paged_reference(absorbed_query(q_nope, q_pe, p, 128), pool,
                                jnp.arange(S // 8, dtype=jnp.int32)[None],
                                jnp.asarray([S], jnp.int32), jnp.arange(S)[None],
                                scale=scale, dv=m.kv_lora_rank)
    absorbed = jnp.einsum("bshr,rhk->bshk", o_lat, p["wv_b"])
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), atol=1e-5)


def serve_logits(model, params, ctx, toks, cache_dtype=jnp.float32, chunk=16, ps=8):
    """Logits of every position of each row of ``toks`` (B, N), fed through
    ``decode_chunk``: ragged prefill chunks of ``chunk`` tokens, then one
    token at a time from position ``chunk + 5``, through the paged pool."""
    B, N = toks.shape
    maxp = -(-N // ps)
    cache = model.init_cache(B, N, cache_dtype, page_size=ps, num_pages=B * maxp + 1)
    pt = jnp.asarray(1 + np.arange(B * maxp).reshape(B, maxp)[:, ::-1].copy(), jnp.int32)
    out = np.zeros((B, N, model.cfg.vocab), np.float32)
    spans, t = [], 0
    while t < N:
        n = chunk if t < chunk + 5 else 1
        spans.append((t, min(n, N - t)))
        t += n
    step = jax.jit(model.decode_chunk, static_argnames=("ctx", "all_logits"))
    for start, n in spans:
        tok = np.zeros((B, chunk), np.int32)
        tok[:, :n] = toks[:, start:start + n]
        logits, cache = step(
            params, jnp.asarray(tok), cache, jnp.full((B,), start, jnp.int32),
            jnp.full((B,), n, jnp.int32), jnp.arange(B, dtype=jnp.int32),
            jnp.full((B,), start == 0), ctx=ctx, page_table=pt, all_logits=True)
        out[:, start:start + n] = np.asarray(logits)[:, :n]
    return out


def test_latent_pool_holds_one_row_a_token(setup):
    cfg, model, _ = setup
    cache = model.init_cache(2, 64, jnp.bfloat16, page_size=8, num_pages=10)
    pool = cache["groups"][1][0]["attn"]
    # 32 latent + 16 rope values a token, padded to one 128-lane tile
    assert set(pool) == {"lat"} and pool["lat"].shape == (2, 10, 8, 128)
    assert kv_bytes_per_token(cache, 80) == 3 * 128 * 2
    with pytest.raises(NotImplementedError):
        model.init_cache(2, 64, kind="dense")


@pytest.mark.parametrize("ctx", [XLA, PALLAS], ids=["xla", "pallas-interpret"])
def test_prefill_then_decode_matches_forward(setup, ctx):
    """Prefill chunks, then decode, through the latent pool give the full
    forward pass's logits at every position (positions past the YaRN
    original context of 64 included)."""
    cfg, model, params = setup
    toks = np.random.default_rng(3).integers(0, cfg.vocab, (2, 72)).astype(np.int32)
    full, _ = model.forward(params, {"tokens": jnp.asarray(toks)}, XLA)
    served = serve_logits(model, params, ctx, toks)
    np.testing.assert_allclose(served, np.asarray(full), atol=F32_ATOL)


def test_bf16_latent_pool_fails_the_f32_tolerance(setup):
    cfg, model, params = setup
    toks = np.random.default_rng(3).integers(0, cfg.vocab, (2, 72)).astype(np.int32)
    full, _ = model.forward(params, {"tokens": jnp.asarray(toks)}, XLA)
    served = serve_logits(model, params, XLA, toks, cache_dtype=jnp.bfloat16)
    assert np.abs(served - np.asarray(full)).max() > F32_ATOL


# ---------------------------------------------------------------- engine
class RecordingLM(LM):
    """Records the logits of the live rows of every served call, in order."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.seen = []

    def decode_chunk(self, params, tokens, cache, starts, nvalid, *a, **k):
        logits, cache = super().decode_chunk(params, tokens, cache, starts, nvalid, *a, **k)

        def record(lg, nv):
            self.seen.extend(np.asarray(lg)[np.asarray(nv) > 0])
        jax.debug.callback(record, logits, nvalid, ordered=True)
        return logits, cache


def test_prefix_cache_and_copy_on_write_over_latent_pages(setup):
    """A session's second turn hits the prefix cache, and a page-aligned
    prompt copies its last shared page on write: both give the logits of the
    same prompts served with the prefix cache off."""
    cfg, _, params = setup
    rng = np.random.default_rng(4)
    doc = rng.integers(1, cfg.vocab, 24).astype(np.int32)             # 3 pages of 8

    def run(prefix_cache, prompts):
        model = RecordingLM(cfg)
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=2, page_size=8, num_pages=48, max_seq=96, prefill_chunk=16,
            greedy=True, enable_prefix_cache=prefix_cache))
        out = []
        for i, p in enumerate(prompts(out)):
            req = Request(req_id=f"t{i}", prompt_tokens=p, max_new_tokens=4)
            model.seen.clear()
            eng.generate([req])
            jax.effects_barrier()
            out.append((req.generated, np.stack(model.seen[-4:])))
        eng.allocator.check_invariants()
        return eng, out

    question = rng.integers(1, cfg.vocab, 5).astype(np.int32)

    def turns(out):
        yield doc                                                      # turn 1
        yield np.concatenate([doc, np.asarray(out[0][0], np.int32), question])   # turn 2
        yield doc                                                      # page-aligned again

    on, served = run(True, turns)
    off, plain = run(False, turns)
    assert on.prefix_cached_tokens >= 24 + 23 and on.allocator.cow_copies >= 1
    assert off.prefix_cached_tokens == 0
    for (g1, l1), (g2, l2) in zip(served, plain):
        assert g1 == g2
        np.testing.assert_allclose(l1, l2, atol=F32_ATOL)


def test_copy_on_write_copies_the_latent_pool(setup):
    cfg, model, params = setup
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=2, page_size=8, num_pages=6, max_seq=32, prefill_chunk=8, greedy=True))
    pool = eng.cache["groups"][1][0]["attn"]["lat"]
    eng.cache["groups"][1][0]["attn"]["lat"] = pool.at[:, 2].set(1.0)
    eng._apply_copies([(2, 4)])
    lat = np.asarray(eng.cache["groups"][1][0]["attn"]["lat"])
    assert (lat[:, 4] == 1.0).all() and (lat[:, 3] == 0.0).all()
    assert eng.stats()["kv_bytes_per_token"] == 3 * 128 * 4


# ---------------------------------------------------------------- costs
def _pool_bytes(cfg, layers):
    groups = (LayerGroup("A", 1, moe_mask="0"), LayerGroup("A", layers - 1, moe_mask="1"))
    model = build_model(cfg.scaled(n_layers=layers, layer_groups=groups))
    cache = jax.eval_shape(lambda: model.init_cache(
        12, 4096, jnp.bfloat16, page_size=16, num_pages=64))
    return kv_bytes_per_token(cache, 64 * 16)


def test_a_cached_token_costs_8960_bytes_against_57344():
    """A 7-layer stage in bf16: 576 latent values a token a layer, in a
    640-lane row (8,064 bytes of values), against DeepSeek-MoE's MHA K and V
    of 16 heads of 128."""
    cfg = get_config("deepseek-v2-lite")
    assert cfg.mla.cache_dim * 2 * 7 == 8064
    assert _pool_bytes(cfg, 7) == 640 * 2 * 7 == 8960
    assert _pool_bytes(get_config("deepseek-moe-16b"), 7) == 57344


# ---------------------------------------------------------------- router
@pytest.mark.parametrize("norm", [True, False])
def test_router_norm_topk_matches_a_hand_computation(norm):
    xf = jnp.asarray([[1.0, 0.0], [0.0, 2.0]])
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.5, 1.0, 0.0]])
    topw, topi, _ = router_topk(xf, w, 2, norm)
    probs = [np.exp([2.0, 1.0, 0.0, -1.0]), np.exp([0.0, 1.0, 2.0, 0.0])]
    probs = [p / p.sum() for p in probs]
    want_i = [[0, 1], [2, 1]]
    want_w = [[p[i] for i in ii] for p, ii in zip(probs, want_i)]
    if norm:
        want_w = [[x / sum(ww) for x in ww] for ww in want_w]
    np.testing.assert_array_equal(np.asarray(topi), want_i)
    np.testing.assert_allclose(np.asarray(topw), want_w, rtol=1e-6)


def test_unnormalized_routing_reaches_the_model():
    """``norm_topk`` False is what the published DeepSeek-V2-Lite routes
    with, and it changes the layer's output."""
    assert get_config("deepseek-v2-lite").moe.norm_topk is False
    outs = []
    for norm in (True, False):
        cfg = tiny_mla(norm_topk=norm)
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        toks = jnp.asarray(np.arange(16)[None] % cfg.vocab, jnp.int32)
        outs.append(np.asarray(model.forward(params, {"tokens": toks}, XLA)[0]))
    assert np.abs(outs[0] - outs[1]).max() > 1e-3
