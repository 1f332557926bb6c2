#!/usr/bin/env python3
"""Serve 2-layer Mixtral-8x7B at its published widths on a TPU, end to end.

The run goes through the path a user's request takes -- gateway ->
ReplicaRouter -> Replica -> InferenceEngine -- with random bf16 weights made
from a seed, and checks that:

  * the engine's two step programs (the prefill pack and the decode sweep)
    hold the Pallas kernels as compiled TPU custom calls;
  * one prefill chunk and one decode step give the same logits on the
    Pallas path as on the XLA reference path, on the same params, within
    ``LOGIT_TOL``;
  * every request completes with all its tokens.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four one-chip replicas behind the
                                        # router, compared with one replica

It refuses to run anywhere but on a TPU. The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache`` in the checkout.
Times printed are from one smoke run, not a benchmark. The last line of
stdout is one JSON object: {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import functools
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs nowhere

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
try:
    from repro.configs import get_config
    from repro.kernels.backend import compiled_kernels, on_tpu
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_fleet, init_model, serve
    from repro.models import RunCtx
except ImportError as e:  # the script alone, without the repository
    sys.exit(f"chip_smoke: run it from a checkout of the repository ({e})")

SEED = 0
# The 2-layer cut holds 3.16 B params (6.33 GB in bf16), so prompts of up to
# 1000 tokens and 8 slots leave most of a 16 GB chip free.
ENGINE = dict(max_slots=8, page_size=16, num_pages=1024, max_seq=1088,
              prefill_chunk=256, greedy=True, cache_dtype=jnp.bfloat16)
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, (200, 1000), 32
# max |pallas - xla| over max |xla| of one call's logits. Both paths run the
# same bf16 model; they differ in accumulation order and in where bf16
# rounding happens, which moves logits by well under 1%. A wrong page, mask
# or expert moves them by the order of the logits themselves.
LOGIT_TOL = 2e-2
KERNELS = {"chunked_prefill_pallas", "gmm_pallas"}   # the served path's kernels
TIMEOUT_S = 600.0
# seconds of every XLA backend compile in this process, to count the
# compiles that happen inside the serving window (there should be none of
# the step programs: compile_steps made them)
COMPILES: list = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **_: COMPILES.append(secs)
    if event == "/jax/core/compile/backend_compile_duration" else None)


def log(msg: str) -> None:
    print(msg, flush=True)


def smoke_config():
    """Mixtral-8x7B at its published widths, cut to 2 of its 32 layers."""
    full = get_config("mixtral-8x7b")
    return full.scaled(n_layers=2, layer_groups=(
        dataclasses.replace(full.layer_groups[0], repeats=2),))


def _describe(cfg, params) -> None:
    n = sum(x.size for x in jax.tree.leaves(params))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    m = cfg.moe
    log(f"config: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"experts={m.num_experts} top_k={m.top_k} d_ff={m.d_expert} vocab={cfg.vocab}")
    log(f"params: {n} ({nbytes} bytes, {jax.tree.leaves(params)[0].dtype})")


def _prompts(vocab: int, n: int, lens) -> list:
    rng = np.random.default_rng(SEED)
    return [rng.integers(1, vocab, int(L)).astype(np.int32)
            for L in rng.integers(lens[0], lens[1] + 1, n)]


def _device_peak(dev) -> str:
    stats = dev.memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


# ----------------------------------------------------------------- compile
def compile_steps(engine, tag: str) -> None:
    """AOT-compile the engine's two step shapes (the serving steps then
    reuse them) and, on a TPU, require the Pallas kernels in both."""
    for name, lowered in engine.lower_steps().items():
        t0 = time.perf_counter()
        compiled = lowered.compile()
        secs = time.perf_counter() - t0
        kernels = compiled_kernels(compiled.as_text())
        mem = compiled.memory_analysis()
        log(f"compile {tag} {name}: {secs:.2f} s; kernels {sorted(set(kernels))} "
            f"x{len(kernels)}; temp {mem.temp_size_in_bytes} bytes")
        if on_tpu():
            if engine.ctx.attn_backend != "pallas" or engine.ctx.interpret:
                raise AssertionError(f"engine on a TPU serves with {engine.ctx}")
            missing = KERNELS - set(kernels)
            if missing:
                raise AssertionError(f"{name} step lacks compiled kernels {missing}")


# ------------------------------------------------------------ logits check
@functools.lru_cache(maxsize=None)
def _chunk_program(model, ctx):
    def run(params, pool, tokens, start, n, page_table):
        rows = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        return model.decode_chunk(params, tokens, pool, start, n, rows, start == 0,
                                  ctx, page_table)
    return jax.jit(run)


def sequence_logits(model, params, ctx, tokens, engine_kw) -> list:
    """Feed one sequence through ``decode_chunk`` as the engine does: all
    but its last token in chunks of the engine's size, then the last token
    as a decode step. Returns each call's logits (f32, host)."""
    C, ps, max_seq = engine_kw["prefill_chunk"], engine_kw["page_size"], engine_kw["max_seq"]
    maxp = -(-max_seq // ps)
    run = _chunk_program(model, ctx)
    pool = model.init_cache(1, max_seq, engine_kw["cache_dtype"], page_size=ps,
                            num_pages=maxp + 1)
    pt = np.arange(1, maxp + 1, dtype=np.int32)[None]
    out, fed = [], 0
    while fed < len(tokens) - 1:
        n = min(C, len(tokens) - 1 - fed)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :n] = tokens[fed:fed + n]
        logits, pool = run(params, pool, chunk, np.array([fed], np.int32),
                           np.array([n], np.int32), pt)
        out.append(np.asarray(logits[0], np.float32))
        fed += n
    logits, pool = run(params, pool, np.array([[tokens[-1]]], np.int32),
                       np.array([fed], np.int32), np.array([1], np.int32), pt)
    out.append(np.asarray(logits[0], np.float32))
    return out


def _rel_err(a, b) -> float:
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def check_logits(model, params, engine_kw, vocab: int) -> None:
    """Pallas vs the XLA reference on one sequence: two prefill chunks (the
    second ragged, so it attends over a full chunk of history) and one
    decode step. Off a TPU the kernels run in the Pallas interpreter."""
    C = engine_kw["prefill_chunk"]
    tokens = np.random.default_rng(SEED + 1).integers(1, vocab, C + C // 2 + 2)
    common = dict(moe_strategy="dropless", block_q=128, block_kv=128)
    t0 = time.perf_counter()
    pallas = sequence_logits(model, params, RunCtx(attn_backend="pallas",
                                                   interpret=not on_tpu(), **common),
                             tokens, engine_kw)
    xla = sequence_logits(model, params, RunCtx(attn_backend="xla", **common),
                          tokens, engine_kw)
    errs = [_rel_err(p, x) for p, x in zip(pallas, xla)]
    names = [f"chunk{i + 1}" for i in range(len(errs) - 1)] + ["decode"]
    log("logits pallas vs xla (max abs diff / max abs logit): "
        + ", ".join(f"{n} {e:.3e}" for n, e in zip(names, errs))
        + f"; tolerance {LOGIT_TOL}; {time.perf_counter() - t0:.1f} s incl. compile")
    if max(errs) > LOGIT_TOL:
        raise AssertionError(f"pallas logits off the xla reference: {errs}")


# ------------------------------------------------------------------ serving
def serve_prompts(fleet, prompts, max_new: int) -> list:
    """Serve through gateway -> router -> ``fleet``; every request must
    complete with all its tokens. Returns the client-side requests.

    Round robin, because least-loaded reads the replicas' load before the
    gateway's first-connection await and so sends a burst of requests that
    arrive together to one replica."""
    n_compiles = len(COMPILES)
    res = asyncio.run(serve(fleet, prompts, concurrency=len(prompts), max_new=max_new,
                            policy="round_robin", timeout_s=TIMEOUT_S))
    window = COMPILES[n_compiles:]
    bad = [(r.req_id, r.error, len(r.generated)) for r in res.requests
           if r.error or not r.finished or len(r.generated) != max_new]
    if bad:
        raise AssertionError(f"requests that did not complete: {bad}")
    ttft = [r.t5 - r.t0 for r in res.requests]
    gaps = np.concatenate([np.diff(r.token_times) for r in res.requests])
    log(f"served {len(res.requests)} requests x {max_new} tokens in "
        f"{res.t_end - res.t_start:.2f} s (smoke run, not a benchmark): "
        f"TTFT p50 {np.median(ttft):.3f} s max {max(ttft):.3f} s; "
        f"inter-token p50 {np.median(gaps) * 1e3:.1f} ms max {gaps.max() * 1e3:.1f} ms; "
        f"{len(window)} compiles while serving ({sum(window):.2f} s)")
    return sorted(res.requests, key=lambda r: int(r.req_id.split("-")[1]))


def run_one_chip(cfg, engine_kw, *, dtype, n_requests: int, prompt_len,
                 max_new: int) -> list:
    t0 = time.perf_counter()
    model, params = init_model(cfg, seed=SEED, dtype=dtype)
    jax.block_until_ready(params)
    _describe(cfg, params)
    log(f"init params: {time.perf_counter() - t0:.2f} s")
    fleet = build_fleet(model, params, 1, engine_kwargs=engine_kw)
    try:
        compile_steps(fleet[0].engine, "replica0")
        check_logits(model, params, engine_kw, cfg.vocab)
        reqs = serve_prompts(fleet, _prompts(cfg.vocab, n_requests, prompt_len), max_new)
    finally:
        for r in fleet:
            r.stop()
    log(f"peak bytes in use: {_device_peak(jax.devices()[0])}")
    return reqs


# -------------------------------------------------------------- four chips
def _placement(engine) -> set:
    return {d for tree in (engine.params, engine.cache)
            for x in jax.tree.leaves(tree) for d in x.devices()}


def run_four_chips(cfg, engine_kw, *, dtype, n_requests: int, prompt_len,
                   max_new: int) -> None:
    """Four one-chip replicas behind the router against one replica, on the
    same params and prompts: each replica's arrays on its own device, every
    request complete, and the same greedy tokens (or, where a token flips,
    a near-tie in the logits within ``LOGIT_TOL``)."""
    devices = jax.devices()
    if len(devices) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, found {len(devices)}")
    model, params = init_model(cfg, seed=SEED, dtype=dtype)
    _describe(cfg, params)
    prompts = _prompts(cfg.vocab, n_requests, prompt_len)
    runs = {}
    for n in (1, 4):
        fleet = build_fleet(model, params, n, engine_kwargs=engine_kw)
        try:
            for i, rep in enumerate(fleet):
                where = _placement(rep.engine)
                log(f"{n} replica(s): {rep.replica_id} params+pool on {sorted(map(str, where))}")
                if where != {devices[i]}:
                    raise AssertionError(f"{rep.replica_id} is not on {devices[i]} alone")
                compile_steps(rep.engine, rep.replica_id)
            runs[n] = serve_prompts(fleet, prompts, max_new)
        finally:
            for r in fleet:
                r.stop()
    served = {r.replica_id for r in runs[4]}
    log(f"4 replicas: requests served by {sorted(served)}")
    if len(served) != 4:
        raise AssertionError(f"the router used {len(served)} of 4 replicas")
    flips = 0
    for p, one, four in zip(prompts, runs[1], runs[4]):
        if one.generated == four.generated:
            continue
        flips += 1
        j = next(k for k, (a, b) in enumerate(zip(one.generated, four.generated)) if a != b)
        seq = np.concatenate([p, np.asarray(one.generated[:j], np.int32)])
        logits = sequence_logits(model, params, fleet[0].engine.ctx, seq, engine_kw)[-1]
        a, b = one.generated[j], four.generated[j]
        gap = abs(logits[a] - logits[b]) / np.max(np.abs(logits))
        log(f"{one.req_id}: token {j} is {a} on 1 replica, {b} on 4; logit gap {gap:.3e}")
        if gap > LOGIT_TOL:
            raise AssertionError(f"{one.req_id} differs at token {j} beyond a near-tie")
    log(f"outputs: {len(prompts) - flips} of {len(prompts)} requests identical to one "
        f"replica, {flips} near-tie flips")
    for d in devices[:4]:
        log(f"peak bytes in use {d}: {_device_peak(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the four-replica phase and its one-replica comparison")
    args = ap.parse_args(argv)
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default backend is {platform!r}",
              file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    run = run_four_chips if args.four_chips else run_one_chip
    run(smoke_config(), ENGINE, dtype=jnp.bfloat16, n_requests=N_REQUESTS,
        prompt_len=PROMPT_LEN, max_new=MAX_NEW)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
