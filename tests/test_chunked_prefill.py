"""Chunked paged prefill (DESIGN.md §2): kernel oracle equivalence, chunked
prefill logits == the forward pass's across chunk sizes (incl. ragged prompts),
preempt-then-resume determinism, and the per-iteration token budget."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.core.engine import EngineConfig, InferenceEngine
from repro.core.metrics import Request
from repro.kernels.paged_attention import (chunked_prefill_attention,
                                           chunked_prefill_reference)
from repro.kernels.paged_attention import kernel
from repro.models import RunCtx, build_model

CTX = RunCtx(attn_backend="xla", moe_strategy="dropless", block_q=128, block_kv=128)

# atol for chunked-vs-forward logits: both paths compute attention and
# softmax in f32; the differences are reduction-order only.
LOGIT_ATOL = 2e-3


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------- kernel
def _bruteforce(q, kp, vp, pt, starts, lengths, window, softcap):
    """Per query and head, in numpy: softmax over the kv positions the query
    may see, through the page table."""
    B, C, H, D = q.shape
    _, Hkv, ps, _ = kp.shape
    maxp = pt.shape[1]
    kg = kp[pt].transpose(0, 1, 3, 2, 4).reshape(B, maxp * ps, Hkv, D)
    vg = vp[pt].transpose(0, 1, 3, 2, 4).reshape(B, maxp * ps, Hkv, D)
    kv = np.arange(maxp * ps)
    oracle = np.zeros((B, C, H, D), np.float32)
    for b in range(B):
        for i in range(C):
            p_abs = int(starts[b]) + i
            for h in range(H):
                hk = h // (H // Hkv)
                s = (kg[b, :, hk] @ q[b, i, h]) * (D ** -0.5)
                if softcap > 0:
                    s = softcap * np.tanh(s / softcap)
                m = (kv < int(lengths[b])) & (kv <= p_abs)
                if window > 0:
                    m &= kv > p_abs - window
                s = np.where(m, s, -1e30)
                w = np.where(m, np.exp(s - s.max()), 0.0)
                if w.sum() > 0:
                    w /= w.sum()
                oracle[b, i, h] = w @ vg[b, :, hk]
    return oracle


# Rows of the swept cases, by resident length: a padding row (0), one page,
# exactly one block of pages, one past it, the whole table, and a chunk
# whose 5-token window starts on the last position of the first block. The
# table (20 pages of 16) is not a whole number of blocks; the chunk ends at
# the row's length, as the engine issues it.
SWEPT_LENGTHS = ("zero", "page", "block", "block+1", "table", "window-edge")


def _case(C, G, window, softcap, shuffled, head_blocks=1, lengths=SWEPT_LENGTHS):
    return dict(C=C, G=G, window=window, softcap=softcap, shuffled=shuffled,
                head_blocks=head_blocks, lengths=lengths)


@pytest.mark.parametrize("case", [
    # ragged chunks in a table of one block (4-token pages, 8 of them)
    pytest.param(dict(window=0), id="0"),
    pytest.param(dict(window=5), id="5"),
    pytest.param(_case(1, 1, 0, 0.0, True), id="C1-mha-shuffled"),
    pytest.param(_case(1, 4, 5, 30.0, True), id="C1-gqa-window-softcap-shuffled"),
    pytest.param(_case(1, 4, 0, 0.0, False), id="C1-gqa"),
    pytest.param(_case(8, 1, 5, 0.0, False), id="C8-mha-window"),
    pytest.param(_case(8, 4, 0, 30.0, True), id="C8-gqa-softcap-shuffled"),
    pytest.param(_case(8, 4, 5, 0.0, True), id="C8-gqa-window-shuffled"),
    pytest.param(_case(8, 4, 0, 0.0, True, head_blocks=2), id="C8-gqa-head-blocks"),
    pytest.param(_case(8, 4, 5, 0.0, True, head_blocks=2), id="C8-gqa-window-head-blocks"),
    pytest.param(_case(1, 4, 0, 0.0, True, lengths=("page", "zero", "block+1")),
                 id="C1-gqa-padding-between-rows"),
])
def test_chunked_kernel_matches_bruteforce(case, monkeypatch):
    rng = np.random.default_rng(0)
    if "C" not in case:
        B, C, H, Hkv, D, ps, maxp = 3, 8, 4, 2, 16, 4, 8
        starts = np.array([5, 0, 13])
        nvalid = np.array([8, 6, 3])
        lengths = starts + nvalid
        pt = np.asarray([[1 + b * maxp + i for i in range(maxp)] for b in range(B)])
        window, softcap = case["window"], 0.0
    else:
        C, G, window, softcap = case["C"], case["G"], case["window"], case["softcap"]
        Hkv, D, ps, maxp = 2, 16, 16, 20
        H, B = Hkv * G, len(case["lengths"])
        ppb, heads = kernel.block_sizes(ps, maxp, C * G, Hkv, D, 4, 4)
        assert (ppb, heads) == (16, Hkv) and maxp % ppb
        if case["head_blocks"] > 1:     # a budget that holds fewer kv heads
            heads = Hkv // case["head_blocks"]
            monkeypatch.setattr(kernel, "VMEM_BUDGET", kernel._vmem_bytes(
                ppb, heads, ps, C * G, D, 4, 4))
            assert kernel.block_sizes(ps, maxp, C * G, Hkv, D, 4, 4) == (ppb, heads)
            jax.clear_caches()
        at = {"zero": 0, "page": ps, "block": ppb * ps, "block+1": ppb * ps + 1,
              "table": maxp * ps, "window-edge": ppb * ps + 3 + C}
        lengths = np.array([at[n] for n in case["lengths"]])
        nvalid = np.minimum(lengths, C)
        starts = lengths - nvalid
        if case["shuffled"]:
            # pages scattered over the pool; entries past a row's resident
            # pages are out of range, so a read of one would fail
            pt = np.full((B, maxp), -1)
            pages = rng.permutation(np.arange(1, B * maxp + 1))
            for b in range(B):
                used = -(-lengths[b] // ps)
                pt[b, :used], pages = pages[:used], pages[used:]
        else:
            pt = np.asarray([[1 + b * maxp + i for i in range(maxp)] for b in range(B)])
    P = B * maxp + 1
    kp = rng.standard_normal((P, Hkv, ps, D)).astype(np.float32)
    vp = rng.standard_normal((P, Hkv, ps, D)).astype(np.float32)
    q = rng.standard_normal((B, C, H, D)).astype(np.float32)
    qpos = jnp.asarray(starts[:, None] + np.arange(C)[None], jnp.int32)
    oracle = _bruteforce(q, kp, vp, pt, starts, lengths, window, softcap)

    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt, jnp.int32),
            jnp.asarray(lengths, jnp.int32), qpos)
    ref = chunked_prefill_reference(*args, scale=D ** -0.5, softcap=softcap, window=window)
    pal = chunked_prefill_attention(*args, scale=D ** -0.5, softcap=softcap,
                                    window=window, backend="pallas", interpret=True)
    if case.get("head_blocks", 1) > 1:
        jax.clear_caches()
    for b in range(B):
        n = nvalid[b]
        assert np.abs(np.asarray(ref)[b, :n] - oracle[b, :n]).max(initial=0) < 1e-5
        assert np.abs(np.asarray(pal)[b, :n] - oracle[b, :n]).max(initial=0) < 1e-5
    # a padding row writes zeros
    assert not np.asarray(pal)[lengths == 0].any()


# ---------------------------------------------------------------- model
@pytest.mark.parametrize("chunk", [3, 5, 13, 16])
def test_chunked_prefill_matches_dense_logits(setup, chunk):
    """Prompt length 13 is not divisible by chunks 3/5/16; chunk 13 is the
    whole-prompt case. All must match the teacher-forced forward pass at the
    prompt's last position and at every decoded one."""
    cfg, model, params = setup
    S, gen, ps, maxp = 13, 4, 4, 16
    r = np.random.default_rng(2)
    toks = r.integers(0, cfg.vocab, S + gen).astype(np.int32)

    full, _ = model.forward(params, {"tokens": jnp.asarray(toks)[None]}, CTX)
    ref = [np.asarray(full[0, i]) for i in range(S - 1, S + gen)]

    paged = model.init_cache(2, 64, jnp.float32, page_size=ps, num_pages=64)
    pt = jnp.asarray(np.arange(1, maxp + 1, dtype=np.int32)[None])
    slot = jnp.asarray([1], jnp.int32)
    out = []
    fed, firstc = 0, True
    while fed < S:
        n = min(chunk, S - fed)
        tk = np.zeros((1, chunk), np.int32)
        tk[0, :n] = toks[fed:fed + n]
        lg, paged = model.decode_chunk(
            params, jnp.asarray(tk), paged, jnp.asarray([fed], jnp.int32),
            jnp.asarray([n], jnp.int32), slot, jnp.asarray([firstc]), CTX, pt)
        fed += n
        firstc = False
    out.append(np.asarray(lg[0]))
    for i in range(gen):
        lg, paged = model.decode_chunk(
            params, jnp.asarray(toks[S + i:S + i + 1])[None], paged,
            jnp.asarray([S + i], jnp.int32), jnp.asarray([1], jnp.int32),
            slot, jnp.asarray([False]), CTX, pt)
        out.append(np.asarray(lg[0]))
    errs = [float(np.abs(a - b).max()) for a, b in zip(ref, out)]
    assert max(errs) < LOGIT_ATOL, errs


def _ref_greedy(model, params, prompt, n):
    """Greedy decoding by the teacher-forced forward pass: the prompt and
    the tokens chosen so far, padded to one length (the model is causal, so
    the padding changes no earlier position's logits)."""
    seq = np.zeros((1, len(prompt) + n), np.int32)
    seq[0, :len(prompt)] = prompt
    logits = jax.jit(lambda p, t: model.forward(p, {"tokens": t}, CTX)[0])
    out = []
    for i in range(len(prompt), len(prompt) + n):
        out.append(int(jnp.argmax(logits(params, jnp.asarray(seq))[0, i - 1])))
        seq[0, i] = out[-1]
    return out


# ---------------------------------------------------------------- engine
def test_engine_multi_chunk_prefill_matches_reference(setup):
    """Chunk smaller than the prompt: prefill spans several iterations while
    other slots decode, and greedy output still matches the pure model."""
    cfg, model, params = setup
    r = np.random.default_rng(3)
    prompts = [r.integers(1, cfg.vocab, int(n)).astype(np.int32)
               for n in [19, 7, 26, 11]]
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=3, page_size=8, num_pages=64, max_seq=64,
        prefill_chunk=8, token_budget=12, greedy=True))
    reqs = [Request(req_id=f"c{i}", prompt_tokens=p, max_new_tokens=10)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    eng.allocator.check_invariants()
    assert max(eng.iter_token_counts) <= 12
    for req, p in zip(reqs, prompts):
        assert req.finished
        assert req.generated == _ref_greedy(model, params, p, 10)


def test_engine_preempt_resume_reproduces_tokens(setup):
    """Few pages force mid-stream preemption of partially-decoded requests;
    resumed slots (re-prefilled in chunks) must emit identical tokens."""
    cfg, model, params = setup
    r = np.random.default_rng(4)
    prompts = [r.integers(1, cfg.vocab, 12).astype(np.int32) for _ in range(4)]
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=3, page_size=8, num_pages=8, max_seq=64,
        prefill_chunk=5, token_budget=9, greedy=True))
    reqs = [Request(req_id=f"p{i}", prompt_tokens=p, max_new_tokens=12)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    eng.allocator.check_invariants()
    assert eng.scheduler.n_preemptions > 0, "test must exercise preemption"
    for req, p in zip(reqs, prompts):
        assert req.finished
        assert req.generated == _ref_greedy(model, params, p, 12)


def test_iteration_token_budget_held_under_load(setup):
    """64 concurrent requests with mixed prompt lengths: no iteration may
    exceed the configured token budget."""
    cfg, model, params = setup
    budget = 24
    r = np.random.default_rng(5)
    prompts = [r.integers(1, cfg.vocab, int(r.integers(4, 40))).astype(np.int32)
               for _ in range(64)]
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=8, page_size=8, num_pages=256, max_seq=64,
        prefill_chunk=8, token_budget=budget, greedy=True))
    reqs = [Request(req_id=f"b{i}", prompt_tokens=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    assert all(q.finished for q in reqs)
    counts = list(eng.iter_token_counts)
    assert max(counts) <= budget, max(counts)
    # the pack must actually mix prefill chunks and decode tokens
    assert eng.prefill_tokens > 0 and eng.decode_tokens > 0


def test_oversized_prompt_fails_fast(setup):
    """A prompt that can never fit max_seq must finish immediately with zero
    tokens (the legacy dense-prefill engine crashed on these)."""
    cfg, model, params = setup
    r = np.random.default_rng(7)
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=2, page_size=8, num_pages=64, max_seq=32,
        prefill_chunk=8, greedy=True))
    big = Request(req_id="big", prompt_tokens=r.integers(1, cfg.vocab, 50).astype(np.int32),
                  max_new_tokens=4)
    ok = Request(req_id="ok", prompt_tokens=r.integers(1, cfg.vocab, 6).astype(np.int32),
                 max_new_tokens=4)
    eng.generate([big, ok], max_steps=200)
    assert big.finished and len(big.generated) == 0
    assert ok.finished and len(ok.generated) == 4
    eng.allocator.check_invariants()


def test_no_dense_cache_on_serving_path(setup):
    """The model has one serving path, ``decode_chunk`` over the paged pool:
    no dense-cache prefill or one-token decode step remains beside it, and
    the engine keeps none of the legacy prefill/scatter hooks."""
    cfg, model, params = setup
    from repro.models.transformer import LM
    for attr in ("prefill", "decode_step"):
        assert not hasattr(LM, attr)
    for attr in ("_run_prefill", "_scatter_fn", "_prefill_fn", "_bucket"):
        assert not hasattr(InferenceEngine, attr)
    with pytest.raises(NotImplementedError):
        model.init_cache(1, 64, kind="dense")
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=2, page_size=8, num_pages=32, max_seq=64,
        prefill_chunk=8, greedy=True))
    r = np.random.default_rng(6)
    reqs = [Request(req_id="d0", prompt_tokens=r.integers(1, cfg.vocab, 9).astype(np.int32),
                    max_new_tokens=4)]
    eng.generate(reqs)
    assert reqs[0].finished
    assert reqs[0].generated == _ref_greedy(model, params, reqs[0].prompt_tokens, 4)
