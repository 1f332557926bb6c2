"""Training step factory: loss -> grad -> (optional int8 grad compression
with error feedback) -> AdamW. Pure function of (params, opt_state, batch),
jit/pjit-able with sharded params (FSDP rules from distributed.sharding).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.collectives import (compress_grads_with_feedback,
                                           decompress_grads, zeros_error_like)
from repro.models import LM, RunCtx
from repro.training.optimizer import adamw_init, adamw_update, cosine_schedule


@dataclass(frozen=True)
class TrainConfig:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    aux_weight: float = 0.01
    grad_compression: bool = False     # int8 + error feedback
    remat: bool = True
    xent_chunk: int = 0                # >0: sequence-chunked cross-entropy
    microbatches: int = 1              # >1: gradient accumulation (memory)


def make_train_step(model: LM, tcfg: TrainConfig, ctx: Optional[RunCtx] = None
                    ) -> Tuple[Callable, Callable]:
    """Returns (init_fn(params) -> state, step_fn(params, state, batch) ->
    (params, state, metrics)). state = (AdamWState, error_feedback|None)."""
    ctx = ctx or RunCtx(attn_backend="xla", moe_strategy="capacity",
                        remat=tcfg.remat)

    def init_fn(params):
        err = zeros_error_like(params) if tcfg.grad_compression else None
        return (adamw_init(params), err)

    def step_fn(params, state, batch):
        opt_state, err = state

        def loss_fn(p, b):
            loss, metrics = model.loss(p, b, ctx, aux_weight=tcfg.aux_weight,
                                       xent_chunk=tcfg.xent_chunk)
            return loss, metrics

        nm = tcfg.microbatches
        if nm <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            # gradient accumulation: the activation working set shrinks nm x;
            # grads accumulate in a params-sized f32 buffer.
            mb = jax.tree.map(lambda x: x.reshape(nm, x.shape[0] // nm, *x.shape[1:]),
                              batch)

            def acc_step(carry, b_i):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b_i)
                g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32) / nm,
                                     g_acc, g)
                return (g_acc, l_acc + l / nm), m

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), ms = jax.lax.scan(acc_step, (g0, jnp.zeros((), jnp.float32)), mb)
            metrics = jax.tree.map(lambda x: jnp.mean(x), ms)
        if tcfg.grad_compression:
            # compress -> (all-reduce happens on the quantized tree under
            # GSPMD data-parallel sharding) -> decompress
            qtree, err = compress_grads_with_feedback(grads, err)
            grads = decompress_grads(qtree)
        lr = cosine_schedule(opt_state.step + 1, peak_lr=tcfg.peak_lr,
                             warmup=tcfg.warmup, total=tcfg.total_steps)
        new_params, new_opt = adamw_update(
            grads, opt_state, params, lr=lr,
            weight_decay=tcfg.weight_decay, grad_clip=tcfg.grad_clip)
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["lr"] = lr
        return new_params, (new_opt, err), metrics

    return init_fn, step_fn
