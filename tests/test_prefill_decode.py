"""Serving invariants: prefill+decode == full forward (per family), and paged
decode == dense decode."""
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


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = tiny_config(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S, gen = 2, 20, 6
    r = np.random.default_rng(1)
    toks = jnp.asarray(r.integers(0, cfg.vocab, (B, S + gen)), jnp.int32)
    batch = {"tokens": toks}
    if cfg.encoder is not None:
        batch["frames"] = jnp.asarray(r.standard_normal((B, 12, cfg.d_model)), jnp.float32)
    if cfg.vision is not None:
        batch["patches"] = jnp.asarray(
            r.standard_normal((B, cfg.vision.n_patches, cfg.vision.d_patch)), jnp.float32)
    offset = cfg.vision.n_patches if cfg.vision is not None else 0
    logits_full, _ = model.forward(params, batch, CTX)

    cache = model.init_cache(B, S + gen + offset, jnp.float32, kind="dense",
                             memory_len=12 if cfg.encoder is not None else 0)
    bp = dict(batch)
    bp["tokens"] = toks[:, :S]
    lg, cache = model.prefill(params, bp, cache, CTX)
    errs = [float(jnp.max(jnp.abs(lg - logits_full[:, S - 1 + offset])))]
    for i in range(gen):
        pos = jnp.full((B,), S + i + offset, jnp.int32)
        lg, cache = model.decode_step(params, toks[:, S + i:S + i + 1], cache, pos, CTX)
        errs.append(float(jnp.max(jnp.abs(lg - logits_full[:, S + i + offset]))))
    assert max(errs) < 2e-3, errs


def test_paged_equals_dense_decode():
    cfg = tiny_config("qwen2.5-3b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    B, S, gen, W, ps = 2, 24, 6, 32, 8
    r = np.random.default_rng(1)
    toks = jnp.asarray(r.integers(0, cfg.vocab, (B, S + gen)), jnp.int32)
    dense = model.init_cache(B, W, jnp.float32, kind="dense")
    lg, dense = model.prefill(params, {"tokens": toks[:, :S]}, dense, CTX)
    maxp = W // ps
    paged = model.init_cache(B, W, jnp.float32, kind="paged", page_size=ps,
                             num_pages=B * maxp + 1)
    pt = jnp.asarray([[b * maxp + i for i in range(maxp)] for b in range(B)], jnp.int32)
    for g in range(len(paged["groups"])):
        for pos in range(len(paged["groups"][g])):
            pc, dc = paged["groups"][g][pos], dense["groups"][g][pos]
            if "attn" not in pc:
                continue
            k, v = dc["attn"]["k"], dc["attn"]["v"]
            R, npg = k.shape[0], W // ps
            for name, src in (("kp", k), ("vp", v)):
                buf = pc["attn"][name]
                for b in range(B):
                    buf = buf.at[:, pt[b][:npg]].set(
                        src[:, b].reshape(R, npg, ps, *src.shape[3:])
                        .transpose(0, 1, 3, 2, 4))
                pc["attn"][name] = buf
    cd, cp, errs = dense, paged, []
    for i in range(gen):
        pos = jnp.full((B,), S + i, jnp.int32)
        ld, cd = model.decode_step(params, toks[:, S + i:S + i + 1], cd, pos, CTX)
        lp, cp = model.decode_step(params, toks[:, S + i:S + i + 1], cp, pos, CTX,
                                   page_table=pt, lengths=pos + 1)
        errs.append(float(jnp.max(jnp.abs(ld - lp))))
    assert max(errs) < 1e-4, errs


def test_sliding_window_ring_buffer_decode():
    """gemma-family: local layers with W << context still decode correctly
    (ring buffer) — compare against a model with full-size windows."""
    cfg = tiny_config("gemma2-27b", seq_len=64)
    assert cfg.sliding_window > 0
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(2))
    B, S = 1, 40
    r = np.random.default_rng(3)
    toks = jnp.asarray(r.integers(0, cfg.vocab, (B, S)), jnp.int32)
    logits_full, _ = model.forward(params, {"tokens": toks}, CTX)
    # decode from scratch token by token (prefill of 1 token, then decode)
    cache = model.init_cache(B, S, jnp.float32, kind="dense")
    lg, cache = model.prefill(params, {"tokens": toks[:, :1]}, cache, CTX)
    errs = []
    for i in range(1, S):
        pos = jnp.full((B,), i, jnp.int32)
        lg, cache = model.decode_step(params, toks[:, i:i + 1], cache, pos, CTX)
        errs.append(float(jnp.max(jnp.abs(lg - logits_full[:, i]))))
    assert max(errs) < 2e-3, max(errs)
