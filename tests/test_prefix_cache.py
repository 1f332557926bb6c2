"""Shared-prefix KV cache (DESIGN.md §2): trie semantics, warm requests skip
prefill and reproduce no-sharing outputs, COW on page-aligned prompts,
preempt/resume/cancel with shared pages leak nothing, SSM/encdec gating."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.core.engine import EngineConfig, InferenceEngine
from repro.core.kv_cache import PagedAllocator, PrefixCache
from repro.core.metrics import Request
from repro.models import build_model

PS = 8  # page size used throughout


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, *, cache=True, pages=64, slots=4, chunk=PS,
            budget=0, max_seq=64, cache_dtype=jnp.float32):
    return InferenceEngine(model, params, EngineConfig(
        max_slots=slots, page_size=PS, num_pages=pages, max_seq=max_seq,
        prefill_chunk=chunk, token_budget=budget, greedy=True,
        enable_prefix_cache=cache, cache_dtype=cache_dtype))


def _reqs(prompts, max_new=6, tag=""):
    return [Request(req_id=f"{tag}{i}", prompt_tokens=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]


def _reference(model, params, prompts, max_new=6):
    """No-sharing engine output for the same prompts (chunked==dense is
    already pinned by tests/test_chunked_prefill.py)."""
    eng = _engine(model, params, cache=False)
    reqs = _reqs(prompts, max_new, tag="ref")
    eng.generate(reqs)
    return [r.generated for r in reqs]


# ---------------------------------------------------------------- trie
def test_trie_lookup_insert_evict():
    a = PagedAllocator(num_pages=32, page_size=4, max_pages_per_seq=8)
    trie = PrefixCache(a)
    toks = list(range(11))                 # 2 full blocks + ragged tail
    pages = a.allocate(0, len(toks))
    assert trie.lookup(toks) == []         # cold
    trie.insert(toks, pages, 2)
    assert len(trie) == 2
    assert trie.lookup(toks) == pages[:2]  # only full blocks match
    assert trie.lookup(toks[:8]) == pages[:2]
    assert trie.lookup(toks[:7]) == pages[:1]
    # divergent second block: first still hits
    div = toks[:4] + [99, 99, 99, 99]
    assert trie.lookup(div) == pages[:1]
    # free: registered full pages retire, the ragged tail page frees outright
    a.free(0)
    assert a.retired_pages == 2
    for s in range(1, 5):                  # take everything (8 pages per slot)
        a.allocate(s, 4 * 8 if s < 4 else 4 * 7)
    assert len(trie) == 0 and trie.lookup(toks) == []
    a.check_invariants()


# ---------------------------------------------------------------- engine
def test_warm_requests_skip_prefill_and_match(setup):
    cfg, model, params = setup
    r = np.random.default_rng(11)
    prefix = r.integers(1, cfg.vocab, 2 * PS).astype(np.int32)   # 2 full pages
    prompts = [np.concatenate([prefix, r.integers(1, cfg.vocab, int(t)).astype(np.int32)])
               for t in [5, 9, 3, 7]]
    eng = _engine(model, params)
    warm_up = _reqs([prompts[0]], tag="w")
    eng.generate(warm_up)
    fed_cold = eng.prefill_tokens
    reqs = _reqs(prompts, tag="q")
    eng.generate(reqs)
    eng.allocator.check_invariants()
    st = eng.stats()
    assert st["prefix_hit_pages"] >= 2 * len(prompts)
    # each warm request skipped the whole 2-page shared prefix
    assert eng.prefix_cached_tokens == 2 * PS * len(prompts)
    assert eng.prefill_tokens - fed_cold == sum(len(p) - 2 * PS for p in prompts)
    assert [q.generated for q in reqs] == _reference(model, params, prompts)


def test_page_aligned_prompt_triggers_cow_and_matches(setup):
    """Prompt length an exact multiple of the page size: every prompt token
    is cached, so the hit is capped at feed_len-1 and the re-fed last token
    must copy-on-write the final shared page, never mutating it in place."""
    cfg, model, params = setup
    r = np.random.default_rng(12)
    prompt = r.integers(1, cfg.vocab, 2 * PS).astype(np.int32)
    eng = _engine(model, params)
    eng.generate(_reqs([prompt], tag="cold"))
    assert eng.allocator.cow_copies == 0
    reqs = _reqs([prompt], tag="warm")
    eng.generate(reqs)
    eng.allocator.check_invariants()
    assert eng.allocator.cow_copies >= 1
    assert eng.prefix_cached_tokens == 2 * PS - 1
    assert [q.generated for q in reqs] == _reference(model, params, [prompt])


def test_bf16_params_and_pool_through_cow(setup):
    """The chip's dtypes: bf16 params and a bf16 pool through chunked
    prefill, a prefix hit, the COW page copy and greedy sampling give the
    tokens of a bf16 engine without sharing."""
    cfg, model, _ = setup
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    prompt = np.random.default_rng(13).integers(1, cfg.vocab, 2 * PS).astype(np.int32)
    eng = _engine(model, params, cache_dtype=jnp.bfloat16)
    eng.generate(_reqs([prompt], tag="cold"))
    reqs = _reqs([prompt], tag="warm")
    eng.generate(reqs)
    eng.allocator.check_invariants()
    assert eng.allocator.cow_copies >= 1
    assert eng.cache["groups"][0][0]["attn"]["kp"].dtype == jnp.bfloat16
    ref = _engine(model, params, cache=False, cache_dtype=jnp.bfloat16)
    ref_reqs = _reqs([prompt], tag="ref")
    ref.generate(ref_reqs)
    assert reqs[0].generated == ref_reqs[0].generated


def test_preempt_resume_with_shared_pages_no_leak(setup):
    """Page pressure forces preemption of requests holding shared pages; on
    resume they re-hit the trie. Outputs must match the no-sharing engine and
    every reference must be released at the end."""
    cfg, model, params = setup
    r = np.random.default_rng(13)
    prefix = r.integers(1, cfg.vocab, PS).astype(np.int32)
    prompts = [np.concatenate([prefix, r.integers(1, cfg.vocab, 10).astype(np.int32)])
               for _ in range(5)]
    eng = _engine(model, params, pages=8, slots=3, chunk=5, budget=9)
    reqs = _reqs(prompts, max_new=10, tag="pr")
    eng.generate(reqs)
    eng.allocator.check_invariants()
    assert eng.scheduler.n_preemptions > 0, "test must exercise preemption"
    assert all(q.finished for q in reqs)
    assert not eng.allocator._ref, "page references leaked after finish"
    ref_eng = InferenceEngine(model, params, EngineConfig(
        max_slots=3, page_size=PS, num_pages=8, max_seq=64, prefill_chunk=5,
        token_budget=9, greedy=True, enable_prefix_cache=False))
    ref = _reqs(prompts, max_new=10, tag="prref")
    ref_eng.generate(ref)
    assert [q.generated for q in reqs] == [q.generated for q in ref]


def test_cancel_with_shared_pages_no_leak(setup):
    cfg, model, params = setup
    r = np.random.default_rng(14)
    prefix = r.integers(1, cfg.vocab, PS).astype(np.int32)
    prompts = [np.concatenate([prefix, r.integers(1, cfg.vocab, 4).astype(np.int32)])
               for _ in range(3)]
    eng = _engine(model, params)
    eng.generate(_reqs([prompts[0]], tag="seed"))     # populate the trie
    reqs = _reqs(prompts, max_new=16, tag="cx")
    for q in reqs:
        eng.submit(q)
    eng.step()                                        # all admitted, sharing
    assert eng.cancel("cx1")
    eng.generate([])                                  # drain the rest
    eng.allocator.check_invariants()
    assert not eng.allocator._ref
    assert all(q.finished for q in reqs if q.req_id != "cx1")


def test_eviction_under_pool_churn(setup):
    """More distinct prompts than the pool can cache: retired pages must be
    reclaimed (LRU) instead of raising OutOfPages, and outputs stay right."""
    cfg, model, params = setup
    r = np.random.default_rng(15)
    prompts = [r.integers(1, cfg.vocab, 2 * PS + 3).astype(np.int32)
               for _ in range(8)]
    eng = _engine(model, params, pages=13, slots=2)   # 12 usable pages
    reqs = _reqs(prompts, max_new=4, tag="ev")
    eng.generate(reqs)
    eng.allocator.check_invariants()
    assert all(q.finished for q in reqs)
    assert eng.allocator.evicted_pages > 0
    assert [q.generated for q in reqs] == _reference(model, params, prompts, max_new=4)


def test_prefix_cache_gated_off_for_ssm():
    cfg = tiny_config("mamba2-1.3b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        max_slots=2, page_size=8, num_pages=16, max_seq=32, greedy=True))
    assert eng.prefix_cache is None
    assert eng.scheduler.prefix_cache is None
