"""Serving invariants of the one serving path, ``LM.decode_chunk`` over the
paged pool: prefill chunks then one-token chunks give the full forward
pass's logits at every position (per family), the page table's layout does
not change them, and sliding-window layers decode past their window."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.models import RunCtx, build_model

ARCHS = ["qwen2.5-3b", "gemma2-27b", "phi3-mini-3.8b", "mamba2-1.3b",
         "jamba-v0.1-52b", "mixtral-8x7b", "deepseek-moe-16b",
         "seamless-m4t-large-v2", "phi-3-vision-4.2b"]

CTX = RunCtx(attn_backend="xla", moe_strategy="dropless", block_q=8, block_kv=8)
PAGE = 4


def _pool(model, B, kv_len, memory_len=0):
    """A paged cache for B rows of ``kv_len`` positions and each row's
    contiguous page table (page 0 is the null page)."""
    maxp = -(-kv_len // PAGE)
    cache = model.init_cache(B, kv_len, jnp.float32, page_size=PAGE,
                             num_pages=B * maxp + 1, memory_len=memory_len)
    pt = np.arange(1, B * maxp + 1, dtype=np.int32).reshape(B, maxp)
    return cache, pt


def _chunks(prompt: int, width: int, gen: int):
    """(call width, tokens fed) of each call: the prompt in chunks of
    ``width``, the last one ragged where it falls short, then ``gen``
    one-token chunks."""
    pre = [(width, min(width, prompt - i)) for i in range(0, prompt, width)]
    return pre + [(1, 1)] * gen


def _feed(model, params, cache, pt, toks, chunks, **extras):
    """Feed ``toks`` (B, n) through ``decode_chunk`` call by call
    (``_chunks``), every row at once. Returns, per fed position, the logits the
    chunk gave there ((B, vocab) each), and the cache. A prefill chunk (width
    > 1) scores every position (``all_logits``); a one-token chunk gives its
    row's last-position logits, as the engine's decode sweep reads them.
    Encoder frames go with the prefill chunks and patches with the first,
    as the engine passes them."""
    B = toks.shape[0]
    slots = jnp.arange(B, dtype=jnp.int32)
    step = jax.jit(functools.partial(model.decode_chunk, ctx=CTX),
                   static_argnames=("all_logits",))
    out, fed = [], 0
    for w, n in chunks:
        tk = np.zeros((B, w), np.int32)
        tk[:, :n] = toks[:, fed:fed + n]
        kw = {k: v for k, v in extras.items()
              if (k == "frames" and w > 1) or (k == "patches" and fed == 0)}
        lg, cache = step(
            params, jnp.asarray(tk), cache, jnp.full((B,), fed, jnp.int32),
            jnp.full((B,), n, jnp.int32), slots, jnp.full((B,), fed == 0),
            page_table=jnp.asarray(pt), all_logits=w > 1, **kw)
        out += [lg[:, i] for i in range(n)] if w > 1 else [lg]
        fed += n
    return out, cache


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    """Prefill chunks of 7 over a 20-token prompt (the last one ragged),
    then six one-token chunks: every position's logits equal the forward
    pass's (encoder frames and vision patches as the forward pass has them)."""
    cfg = tiny_config(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S, gen, width = 2, 20, 6, 7
    r = np.random.default_rng(1)
    toks = r.integers(0, cfg.vocab, (B, S + gen)).astype(np.int32)
    batch, extras = {"tokens": jnp.asarray(toks)}, {}
    if cfg.encoder is not None:
        batch["frames"] = extras["frames"] = jnp.asarray(
            r.standard_normal((B, 12, cfg.d_model)), jnp.float32)
    if cfg.vision is not None:
        batch["patches"] = extras["patches"] = jnp.asarray(
            r.standard_normal((B, cfg.vision.n_patches, cfg.vision.d_patch)), jnp.float32)
    offset = cfg.vision.n_patches if cfg.vision is not None else 0
    logits_full, _ = model.forward(params, batch, CTX)

    cache, pt = _pool(model, B, S + gen + offset,
                      memory_len=12 if cfg.encoder is not None else 0)
    out, _ = _feed(model, params, cache, pt, toks, _chunks(S, width, gen), **extras)
    assert len(out) == S + gen
    errs = [float(jnp.max(jnp.abs(lg - logits_full[:, i + offset])))
            for i, lg in enumerate(out)]
    assert max(errs) < 2e-3, errs


def test_paged_equals_dense_decode():
    """The pages a row's table names, not where they lie in the pool, decide
    its logits: prefill and decode over a shuffled page table (the rows'
    pages interleaved in the pool) equal those over a contiguous one."""
    cfg = tiny_config("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    B, S, gen = 2, 24, 6
    r = np.random.default_rng(1)
    toks = r.integers(0, cfg.vocab, (B, S + gen)).astype(np.int32)
    chunks = _chunks(S, 8, gen)
    cache, pt = _pool(model, B, S + gen)
    shuffled = (r.permutation(pt.size) + 1).astype(np.int32).reshape(pt.shape)
    assert (shuffled != pt).any()
    contiguous, _ = _feed(model, params, cache, pt, toks, chunks)
    spread, _ = _feed(model, params, cache, shuffled, toks, chunks)
    errs = [float(jnp.max(jnp.abs(a - b))) for a, b in zip(contiguous, spread)]
    assert max(errs) < 1e-5, errs


def test_sliding_window_ring_buffer_decode():
    """gemma-family: local layers whose window (16) is shorter than the
    context (40) decode correctly one token at a time through the paged
    pool, against the forward pass."""
    cfg = tiny_config("gemma2-27b", seq_len=64)
    assert 0 < cfg.sliding_window < 40
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(2))
    B, S = 1, 40
    r = np.random.default_rng(3)
    toks = r.integers(0, cfg.vocab, (B, S)).astype(np.int32)
    logits_full, _ = model.forward(params, {"tokens": jnp.asarray(toks)}, CTX)
    cache, pt = _pool(model, B, S)
    out, _ = _feed(model, params, cache, pt, toks, _chunks(0, 1, S))
    errs = [float(jnp.max(jnp.abs(lg - logits_full[:, i]))) for i, lg in enumerate(out)]
    assert max(errs) < 2e-3, max(errs)
