"""Engine integration: greedy output == pure-model reference (with and
without page-pressure preemption), static mode, cancel, slot reuse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.core.engine import EngineConfig, InferenceEngine, sample_tokens
from repro.core.metrics import Request
from repro.kernels.backend import on_tpu, resolve
from repro.models import RunCtx, build_model

CTX = RunCtx(attn_backend="xla", moe_strategy="dropless", block_q=128, block_kv=128)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


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


@pytest.mark.parametrize("num_pages", [10, 64])
def test_engine_matches_reference(setup, num_pages):
    cfg, model, params = setup
    r = np.random.default_rng(0)
    prompts = [r.integers(1, cfg.vocab, 10).astype(np.int32) for _ in range(5)]
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=3, page_size=8, num_pages=num_pages, max_seq=64,
        prefill_bucket=16, greedy=True))
    reqs = [Request(req_id=f"x{i}", prompt_tokens=p, max_new_tokens=12)
            for i, p in enumerate(prompts)]
    eng.generate(reqs)
    eng.allocator.check_invariants()
    for req, p in zip(reqs, prompts):
        assert req.finished
        assert req.generated == _ref_greedy(model, params, p, 12)


def test_backend_follows_platform(setup):
    """"auto" is resolved once, from the platform: the compiled Pallas
    kernels on a TPU, the XLA references here. The engine's default takes
    it; an explicit choice is kept; an unknown backend is an error."""
    cfg, model, params = setup
    want = "pallas" if on_tpu() else "xla"
    assert RunCtx().attn_backend == want and not RunCtx().interpret
    assert resolve("auto", None) == (want, not on_tpu())
    assert RunCtx(attn_backend="pallas", interpret=True).attn_backend == "pallas"
    eng = InferenceEngine(model, params, EngineConfig(max_slots=2, max_seq=32,
                                                      num_pages=16))
    assert eng.ctx.attn_backend == want and not eng.ctx.interpret
    assert eng.device == jax.devices()[0]
    with pytest.raises(ValueError, match="cuda"):
        RunCtx(attn_backend="cuda")


def test_static_mode_completes(setup):
    cfg, model, params = setup
    r = np.random.default_rng(1)
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=2, page_size=8, num_pages=64, max_seq=64,
        prefill_bucket=16, greedy=True, scheduler="static"))
    reqs = [Request(req_id=f"s{i}", prompt_tokens=r.integers(1, cfg.vocab, 6).astype(np.int32),
                    max_new_tokens=6) for i in range(5)]
    eng.generate(reqs)
    assert all(q.finished and len(q.generated) == 6 for q in reqs)


def test_cancel_frees_slot(setup):
    cfg, model, params = setup
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=1, page_size=8, num_pages=32, max_seq=64,
        prefill_bucket=16, greedy=True))
    a = Request(req_id="a", prompt_tokens=np.arange(1, 6, dtype=np.int32),
                max_new_tokens=50)
    b = Request(req_id="b", prompt_tokens=np.arange(1, 6, dtype=np.int32),
                max_new_tokens=4)
    eng.submit(a)
    eng.submit(b)
    for _ in range(3):
        eng.step()
    assert eng.cancel("a")
    steps = 0
    while eng.has_work() and steps < 100:
        eng.step()
        steps += 1
    assert b.finished and len(b.generated) == 4
    eng.allocator.check_invariants()


def test_sampling_top_p_mass():
    """Sampled token must lie within the smallest set of tokens whose
    cumulative probability reaches top_p."""
    r = np.random.default_rng(0)
    logits = jnp.asarray(r.standard_normal((64, 32)) * 3, jnp.float32)
    top_p, temp = 0.7, 0.8
    toks = sample_tokens(logits, jax.random.PRNGKey(0), temp, top_p, False)
    p = jax.nn.softmax(logits / temp, axis=-1)
    for i, t in enumerate(np.asarray(toks)):
        row = np.asarray(p[i])
        order = np.argsort(-row)
        keep = np.cumsum(row[order]) - row[order] < top_p
        nucleus = set(order[keep].tolist())
        assert int(t) in nucleus


def test_sampling_greedy_is_argmax():
    logits = jnp.asarray(np.random.default_rng(1).standard_normal((8, 16)), jnp.float32)
    toks = sample_tokens(logits, jax.random.PRNGKey(0), 0.5, 0.7, True)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(jnp.argmax(logits, -1)))
