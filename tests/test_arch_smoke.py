"""Per-architecture smoke tests (deliverable f): every assigned arch + the
paper's mixtral, as a REDUCED same-family config — one forward + one train
step on CPU, asserting output shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, tiny_config
from repro.models import RunCtx, build_model
from repro.training.train_step import TrainConfig, make_train_step

CTX = RunCtx(attn_backend="xla", moe_strategy="capacity", block_q=16, block_kv=16)


def _batch(cfg, B=2, S=24, seed=0):
    r = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(r.integers(0, cfg.vocab, (B, S)), jnp.int32),
             "labels": jnp.asarray(r.integers(0, cfg.vocab, (B, S)), jnp.int32)}
    if cfg.encoder is not None:
        batch["frames"] = jnp.asarray(r.standard_normal((B, 12, cfg.d_model)), jnp.float32)
    if cfg.vision is not None:
        batch["patches"] = jnp.asarray(
            r.standard_normal((B, cfg.vision.n_patches, cfg.vision.d_patch)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = tiny_config(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = model.forward(params, batch, CTX)
    S_out = batch["tokens"].shape[1] + (cfg.vision.n_patches if cfg.vision else 0)
    assert logits.shape == (2, S_out, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch):
    cfg = tiny_config(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    init_fn, step_fn = make_train_step(model, TrainConfig(peak_lr=1e-3, remat=True), CTX)
    state = init_fn(params)
    batch = _batch(cfg)
    new_params, state, metrics = jax.jit(step_fn)(params, state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    # params actually changed
    delta = sum(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_param_count_positive(arch):
    cfg = get_config(arch)
    n = cfg.param_count()
    na = cfg.active_param_count()
    assert n > 1e9 and 0 < na <= n
