"""End-to-end behaviour tests for the paper's system: full gateway -> router
-> replicas -> continuous-batching engine path with real streaming, plus the
observability/metrics pipeline, plus a (reduced-mesh) dry-run subprocess."""
import asyncio
import json
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.core import (EngineConfig, Gateway, InferenceEngine, MetricsSink,
                        Replica, ReplicaRouter, RouterConfig,
                        baseline_gateway_config, scale_gateway_config, summarize)
from repro.core.client import merge_engine_timestamps, run_workload
from repro.data.workload import WorkloadSpec, sample_workload
from repro.models import build_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def stack():
    cfg = tiny_config("mixtral-8x7b")      # the paper's model family
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _run(cfg, model, params, gw_cfg, n_requests=10, concurrency=4):
    async def main():
        reps = [Replica(f"r{i}", InferenceEngine(model, params, EngineConfig(
            max_slots=4, page_size=8, num_pages=128, max_seq=128,
            prefill_bucket=16, greedy=True))).start() for i in range(2)]
        sink = MetricsSink()
        router = ReplicaRouter(reps, RouterConfig(policy="least_loaded"), sink=sink)
        gw = Gateway(router, gw_cfg)
        prompts, _ = sample_workload(WorkloadSpec(n_requests=n_requests,
                                                  vocab=cfg.vocab, scale=0.05, seed=7))
        res = await run_workload(gw, prompts, concurrency=concurrency,
                                 max_new_tokens=10, timeout_s=120)
        merge_engine_timestamps(res.requests, gw)
        for r in reps:
            r.stop()
        return res, sink

    return asyncio.run(main())


def test_end_to_end_serving_both_gateways(stack):
    cfg, model, params = stack
    for gw_cfg in (scale_gateway_config(), baseline_gateway_config()):
        res, sink = _run(cfg, model, params, gw_cfg)
        assert all(r.finished for r in res.requests), gw_cfg.name
        assert all(len(r.generated) == 10 for r in res.requests)
        s = summarize(res.requests, res.t_start, res.t_end, 4)
        # lifecycle ordering: t0 <= t1 <= t2 <= t4 <= t5 <= t6, t2 <= t3
        for r in res.requests:
            assert r.t0 <= r.t1 <= r.t2 <= r.t4 <= r.t5 <= r.t6
            assert r.t2 <= r.t3 <= r.t6
        assert s.throughput_tok_s > 0
        counters = sink.snapshot()
        assert counters["requests_completed"] == len(res.requests)
        assert counters["tokens_generated"] == sum(r.n_generated for r in res.requests)


def test_metrics_persisted_to_disk(stack):
    cfg, model, params = stack
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "metrics.jsonl")
        sink = MetricsSink(path)

        async def main():
            rep = Replica("p0", InferenceEngine(model, params, EngineConfig(
                max_slots=2, page_size=8, num_pages=64, max_seq=64,
                prefill_bucket=16, greedy=True))).start()
            router = ReplicaRouter([rep], sink=sink)
            gw = Gateway(router, scale_gateway_config(), sink=sink)
            prompts = [np.arange(1, 9, dtype=np.int32)] * 3
            res = await run_workload(gw, prompts, concurrency=3, max_new_tokens=4)
            rep.stop()
            return res

        asyncio.run(main())
        n = sink.flush()
        assert n >= 3
        lines = [json.loads(ln) for ln in open(path)]
        assert all(ln["kind"] == "request" for ln in lines)
        assert all("engine_latency" in ln for ln in lines)


@pytest.mark.slow
def test_dryrun_subprocess_single_cell():
    """Smoke the real dry-run entry point (512 fake devices) on one cell;
    asserts lower+compile succeeded and the roofline terms exist."""
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2.5-3b",
             "--shape", "train_4k", "--mesh", "single", "--out", d],
            capture_output=True, text=True, timeout=1500, env=env, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.load(open(os.path.join(
            d, "qwen2.5-3b__train_4k__single__tp.json")))
        assert out["compiled_ok"]
        assert out["roofline"]["dominant"] in ("compute", "memory", "collective")
