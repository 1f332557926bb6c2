"""Serving launcher: build a replica fleet + gateway for an --arch config and
drive a synthetic OpenOrca-like workload against it (the reduced config;
``chip_smoke.py`` at the repository root drives the same functions at
published widths on a TPU).

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
      --replicas 2 --concurrency 8 --requests 32 --gateway scale
"""
from __future__ import annotations

import argparse
import asyncio
import json
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import tiny_config
from repro.configs.base import ModelConfig
from repro.core import (EngineConfig, Gateway, InferenceEngine, Replica,
                        ReplicaRouter, RouterConfig, baseline_gateway_config,
                        scale_gateway_config, summarize)
from repro.core.client import ClientResult, merge_engine_timestamps, run_workload
from repro.core.replica import replica_device
from repro.data.workload import WorkloadSpec, sample_workload
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LM, build_model


def init_model(cfg: ModelConfig, *, seed: int = 0, dtype=jnp.float32):
    """The model and its random params, made on the default device in one
    jitted call (bf16 weights never pass through an f32 copy)."""
    model = build_model(cfg)
    params = jax.jit(model.init_params, static_argnums=1)(jax.random.PRNGKey(seed), dtype)
    return model, params


def build_fleet(model: LM, params, n_replicas: int, *, engine_kwargs=None,
                klass: str = "default") -> List[Replica]:
    """``n_replicas`` started replicas; replica *i* serves on
    ``replica_device(i)`` from its own copy of ``params`` put there."""
    kw = dict(max_slots=8, page_size=16, num_pages=256, max_seq=256,
              prefill_bucket=32, greedy=False)
    kw.update(engine_kwargs or {})
    return [Replica(f"{model.cfg.name}-r{i}",
                    InferenceEngine(model, jax.device_put(params, replica_device(i)),
                                    EngineConfig(**kw)),
                    klass=klass).start()
            for i in range(n_replicas)]


async def serve(fleet: List[Replica], prompts: List[np.ndarray], *, concurrency: int,
                max_new: int, gateway_kind: str = "scale",
                policy: str = "least_loaded", timeout_s: float = 60.0) -> ClientResult:
    """Stream ``prompts`` through gateway -> router -> ``fleet`` from a
    closed-loop client; the requests carry client and server timestamps."""
    router = ReplicaRouter(fleet, RouterConfig(policy=policy))
    gw_cfg = scale_gateway_config() if gateway_kind == "scale" else baseline_gateway_config()
    gw = Gateway(router, gw_cfg)
    res = await run_workload(gw, prompts, concurrency=concurrency,
                             max_new_tokens=max_new, timeout_s=timeout_s)
    merge_engine_timestamps(res.requests, gw)
    return res


async def serve_and_measure(arch: str, *, replicas: int, concurrency: int,
                            n_requests: int, gateway_kind: str, policy: str,
                            max_new: int = 24, seed: int = 0):
    cfg = tiny_config(arch)
    model, params = init_model(cfg, seed=seed)
    fleet = build_fleet(model, params, replicas)
    prompts, _ = sample_workload(WorkloadSpec(n_requests=n_requests, vocab=cfg.vocab,
                                              scale=0.05, seed=seed))
    res = await serve(fleet, prompts, concurrency=concurrency, max_new=max_new,
                      gateway_kind=gateway_kind, policy=policy)
    for r in fleet:
        r.stop()
    return summarize(res.requests, res.t_start, res.t_end, concurrency)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--gateway", default="scale", choices=["scale", "baseline"])
    ap.add_argument("--policy", default="least_loaded",
                    choices=["round_robin", "least_loaded", "dynamic"])
    args = ap.parse_args()
    enable_compile_cache()
    s = asyncio.run(serve_and_measure(
        args.arch, replicas=args.replicas, concurrency=args.concurrency,
        n_requests=args.requests, gateway_kind=args.gateway, policy=args.policy))
    print(json.dumps({
        "arch": args.arch, "gateway": args.gateway, "policy": args.policy,
        "concurrency": s.concurrency, "throughput_tok_s": s.throughput_tok_s,
        "mean": s.mean, "p99": s.p99, "timeout_frac": s.timeout_frac,
    }, indent=1))


if __name__ == "__main__":
    main()
