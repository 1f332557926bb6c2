"""Shared harness for the paper-figure benchmarks.

Endpoint emulation (documented in EXPERIMENTS.md): all endpoints run OUR
engine/gateway code on a reduced mixtral-family model; the engine-quality
differences between the paper's endpoints are represented by their defining
*mechanisms*, not fake numbers:

  hf        static batching, sequential slots (transformers+FastAPI behavior)
            + per-step Python-loop overhead
  vllm      continuous batching + paged KV (vLLM's core) + Python scheduler
            overhead per iteration, FastAPI-style gateway
  scalellm  continuous batching + paged KV + zero host overhead + the
            optimized (binary/pooled) gateway

The gateway contrast (json+per-request connections+bounded sync workers vs
msgpack+pool+async) is REAL measured Python; only the connection handshake
latency constant is simulated (no physical network).
"""
from __future__ import annotations

import asyncio
from typing import Dict, Optional

import jax

from repro.configs import tiny_config
from repro.core import (EngineConfig, Gateway, InferenceEngine, Replica,
                        ReplicaRouter, RouterConfig, baseline_gateway_config,
                        scale_gateway_config, summarize)
from repro.core.client import merge_engine_timestamps, run_workload
from repro.core.metrics import BenchmarkSummary
from repro.core.replica import replica_device
from repro.data.workload import WorkloadSpec, sample_workload
from repro.launch.serve import init_model

ARCH = "mixtral-8x7b"          # the paper's evaluation model (reduced config)

ENGINE_STYLES = {
    "hf": dict(scheduler="static", max_slots=1, host_overhead_s=0.002,
               enable_prefix_cache=False),
    "vllm": dict(scheduler="max_utilization", max_slots=8, host_overhead_s=0.001,
                 enable_prefix_cache=False),
    "scalellm": dict(scheduler="max_utilization", max_slots=8, host_overhead_s=0.0),
}

_model_cache: Dict[str, tuple] = {}


def get_model(arch: str = ARCH):
    if arch not in _model_cache:
        cfg = tiny_config(arch)
        _model_cache[arch] = (cfg, *init_model(cfg))
    return _model_cache[arch]


def build_replicas(style: str, n_replicas: int = 1, *, arch: str = ARCH,
                   max_slots: Optional[int] = None, klass: str = "default",
                   tracer=None, engine_overrides: Optional[dict] = None,
                   injector=None, step_watchdog_s: Optional[float] = None):
    cfg, model, params = get_model(arch)
    kw = dict(page_size=8, num_pages=256, max_seq=192, prefill_bucket=16,
              greedy=True, **ENGINE_STYLES[style])
    if max_slots is not None:
        kw["max_slots"] = max_slots
    if engine_overrides:
        kw.update(engine_overrides)
    rkw: dict = {"klass": klass, "injector": injector}
    if step_watchdog_s is not None:
        rkw["step_watchdog_s"] = step_watchdog_s
    return [Replica(f"{style}-{i}",
                    InferenceEngine(model, jax.device_put(params, replica_device(i)),
                                    EngineConfig(**kw), tracer=tracer),
                    **rkw).start() for i in range(n_replicas)]


def run_endpoint(style: str, gateway: str, *, concurrency: int, n_requests: int,
                 n_replicas: int = 1, max_new: int = 10, timeout_s: float = 60.0,
                 policy: str = "least_loaded", seed: int = 0,
                 replicas=None) -> BenchmarkSummary:
    cfg, model, params = get_model()
    fleet = replicas or build_replicas(style, n_replicas)
    router = ReplicaRouter(fleet, RouterConfig(policy=policy))
    gw_cfg = scale_gateway_config() if gateway == "scale" else baseline_gateway_config()
    gw = Gateway(router, gw_cfg)
    prompts, _ = sample_workload(WorkloadSpec(n_requests=n_requests, vocab=cfg.vocab,
                                              scale=0.04, seed=seed))

    async def main():
        return await run_workload(gw, prompts, concurrency=concurrency,
                                  max_new_tokens=max_new, timeout_s=timeout_s)

    res = asyncio.run(main())
    merge_engine_timestamps(res.requests, gw)
    if replicas is None:
        for r in fleet:
            r.stop()
    return summarize(res.requests, res.t_start, res.t_end, concurrency,
                     timeout_s=timeout_s)


def warmup():
    """Compile the jitted prefill/decode once so benches measure serving."""
    run_endpoint("scalellm", "scale", concurrency=2, n_requests=2, max_new=4)


def row(name: str, us_per_call: float, **derived) -> dict:
    return {"name": name, "us_per_call": us_per_call, "derived": derived}


def stamp() -> dict:
    """Provenance for persisted BENCH_*.json payloads: the git revision the
    numbers came from plus a UTC timestamp, so a perf trajectory across PRs
    can be reconstructed from the artifacts alone."""
    import datetime
    import os
    import subprocess
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        rev = "unknown"
    return {"git_rev": rev,
            "timestamp": datetime.datetime.now(datetime.timezone.utc)
                         .isoformat(timespec="seconds")}
