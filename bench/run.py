#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json through the served path, on the chips the
cell asks for, and print its result as one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, holding the cell's chips. It makes the weights on each chip
from the seed, builds one replica per chip behind the program's
``ReplicaRouter`` and ``Gateway``, compiles and warms every shape the
traffic uses, runs the traffic for ``ramp_s`` unmeasured and then for
``--seconds`` measured, and checks what the served path produced against a
plain float32 reference (``bench.check``). With ``--trace 1`` it also
traces a few seconds in the middle of the window and prints the cell's
per-layer metrics in place of its end-to-end ones.

It refuses to measure anywhere but on a TPU with at least the cell's
chips. The compile cache is JAX's persistent cache inside the checkout
(``repro.launch.compile_cache``). ``--control 1`` reads the control (the
reference at a precision below the served one) on the same sample and
judges its tokens in the served tokens' place: ``correct`` and ``check``
are then the control's, and ``served`` holds the served path's numbers.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()            # set-up is timed from here

import argparse
import asyncio
import gc
import json
import os
import resource
import shutil
import sys
import tempfile
import threading
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, Optional

ROOT = Path(__file__).resolve().parents[1]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# the benchmark's own spans, set on the objects it builds
SPAN_STEP, SPAN_PLAN, SPAN_HANDLE = "engine_step", "plan_iteration", "gateway_handle"
TRACE_S = 4.0                  # length of the traced slice, at most a third of the window
MAX_WAIT_S = 60.0              # after the window: wait this long for due first tokens
WARM_PROMPT = 24               # tokens of the warm-up request
STALL_S = 0.4                  # a step this long gets the host's counters over it logged


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class RunRecord:
    """What a finished run hands the per-layer metric readers."""
    cell: dict
    cfg: dict
    arch: ModuleType                   # the configuration's bench/arch module
    widths: dict                       # arch.widths(cfg)
    chips: int
    t_open: float
    t_close: float
    sent: list                         # bench.client.Sent, in send order
    server: Dict[str, object]          # rid -> the gateway's Request (t1, t2, replica_id)
    steps: Dict[str, list]             # replica id -> StepRecords that began in the window
    stats_open: Dict[str, dict]        # replica id -> engine.stats() at the window's open
    stats_close: Dict[str, dict]
    peak: dict                         # bench.peaks entry of the device
    trace: Optional[dict] = None       # --trace 1: ``bench.trace.summarize``
    plans: Dict[tuple, dict] = field(default_factory=dict)   # (replica id, step) -> work


# ------------------------------------------------------------------ tracing
def instrument(replica, plans: Dict[tuple, dict]) -> None:
    """Host spans on the replica's engine step and scheduler plan, and a
    record of each step's planned work (row starts, chunk and context
    lengths), read before the step changes them."""
    import jax

    eng, rid = replica.engine, replica.replica_id
    step, plan = eng.step, eng.scheduler.plan_iteration

    def traced_step():
        with jax.profiler.TraceAnnotation(SPAN_STEP, replica=rid, step=eng.steps + 1):
            return step()

    def traced_plan(*a, **k):
        with jax.profiler.TraceAnnotation(SPAN_PLAN):
            p = plan(*a, **k)
        plans[(rid, eng.steps)] = {"prefill": [(st.fed, n) for st, n in p.prefill],
                                   "decode": [st.fed for st in p.decode]}
        return p

    eng.step, eng.scheduler.plan_iteration = traced_step, traced_plan


def instrument_gateway(gateway) -> None:
    import jax

    handle = gateway.handle

    async def traced_handle(*a, **k):
        with jax.profiler.TraceAnnotation(SPAN_HANDLE):
            return await handle(*a, **k)

    gateway.handle = traced_handle


class Profiler:
    """Traces [t0, t0 + secs) of the window from a thread of its own, so
    the serving threads and the client's loop go on meanwhile."""

    def __init__(self, secs: float):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.t0, self.secs = 0.0, secs
        self.thread = threading.Thread(target=self._run, name="profiler")
        self.error: Optional[BaseException] = None

    def _run(self) -> None:
        import jax
        try:
            time.sleep(max(0.0, self.t0 - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # host spans only: ours and the runtime's
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            time.sleep(self.secs)
            jax.profiler.stop_trace()
        except Exception as e:          # reported after the run; the run goes on
            self.error = e

    def path(self) -> Optional[str]:
        import glob
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        return found[0] if found else None


# ------------------------------------------------------------------ the run
def run_cell(root: Path, bench: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, devices: list, control: bool = False,
             t_process: float = T_PROCESS, window_tokens: int = 0) -> dict:
    """One run of ``cell``. ``window_tokens`` > 0 closes the window once that
    many tokens reached their clients inside it (``seconds`` then caps it):
    a test's sample then holds the same work however loaded its machine is."""
    import jax
    import numpy as np

    from bench import check, e2e, registry
    from bench.client import drive, now
    from bench.model import engine_kwargs, make_weights, param_bytes, weight_dtype
    from bench.peaks import peaks
    from bench.watch import Watch
    from repro.core import (EngineConfig, Gateway, InferenceEngine, Replica,
                            ReplicaRouter, RouterConfig, scale_gateway_config)
    from repro.core.metrics import Request
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model

    cfg = registry.config(root, bench, cell["config"])
    mix = registry.traffic(root, cell["traffic"])
    limits = registry.limits(root, cell["name"])
    gen = registry.generator(root, mix["kind"])
    arch = registry.arch(root, cfg["arch"])
    ref = registry.reference(root, cfg["reference"])
    chips = cell["chips"]
    devs = devices[:chips]
    peak = peaks(devs[0].device_kind) if devs[0].platform == "tpu" else {}

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = Watch().install()

    # ---- weights, replicas, warm-up: every shape the traffic uses
    model = build_model(arch.program_config(cfg))
    weights = [make_weights(model, seed, weight_dtype(cfg), d, getattr(arch, "FAN_IN", None))
               for d in devs]
    jax.block_until_ready(weights)
    log(f"weights: {param_bytes(weights[0])} bytes per chip, "
        f"{time.perf_counter() - t_process:.2f} s")
    ekw = engine_kwargs(cfg)
    replicas = [Replica(f"{cfg['name']}-r{i}", InferenceEngine(model, w, EngineConfig(**ekw)))
                for i, w in enumerate(weights)]
    warm = np.random.default_rng(seed).integers(1, cfg["vocab_size"], WARM_PROMPT)
    for r in replicas:
        r.engine._apply_copies([(0, 0)])          # the copy-on-write program (null page)
        r.engine.generate([Request(f"warm-{r.replica_id}", warm.astype(np.int32),
                                   max_new_tokens=3)])
    compiles = watch.compile_seconds()
    log(f"warm: {len(compiles)} compiles ({sum(compiles):.2f} s), "
        f"{time.perf_counter() - t_process:.2f} s")
    plans: Dict[tuple, dict] = {}
    for r in replicas:
        if trace:
            instrument(r, plans)
        r.start()
    gateway = Gateway(ReplicaRouter(replicas, RouterConfig()), scale_gateway_config())
    if trace:
        instrument_gateway(gateway)

    ramp = float(mix.get("ramp_s", 0.0))
    source = gen.make(mix, seed, cfg["vocab_size"], ramp + seconds)
    snaps: Dict[str, Dict[str, dict]] = {}

    async def serve():
        t_start = now()
        t_open, t_close = t_start + ramp, t_start + ramp + seconds
        if profiler is not None:              # a slice in the middle of the window
            profiler.t0 = t_open + (seconds - profiler.secs) / 2
            profiler.thread.start()

        sent: list = []
        stop = asyncio.Event() if window_tokens else None
        closed_at = {}

        async def snapshot(at: float, key: str):
            if key == "close" and stop is not None:
                await stop.wait()
            else:
                await asyncio.sleep(max(0.0, at - now()))
            snaps[key] = {r.replica_id: r.engine.stats() for r in replicas}

        async def close_on_tokens():
            while now() < t_close and e2e.tokens_in(sent, t_open, t_close) < window_tokens:
                await asyncio.sleep(0.02)
            closed_at["t"] = min(now(), t_close)
            stop.set()

        marks = [asyncio.ensure_future(snapshot(t_open, "open")),
                 asyncio.ensure_future(snapshot(t_close, "close"))]
        if stop is not None:
            marks.append(asyncio.ensure_future(close_on_tokens()))
        await drive(gateway, source, t_start=t_start, t_close=t_close, max_wait_s=MAX_WAIT_S,
                    sent=sent, stop=stop)
        await asyncio.gather(*marks)
        # no replica may deliver into the loop once it has closed
        await asyncio.get_running_loop().run_in_executor(
            None, lambda: [r.stop() for r in replicas])
        return sent, t_open, closed_at.get("t", t_close)

    profiler = Profiler(min(TRACE_S, seconds / 3)) if trace else None
    sent, t_open, t_close = asyncio.run(serve())
    if profiler is not None:
        profiler.thread.join(timeout=120)
    setup_s = t_open - t_process
    window_compiles = watch.compile_seconds(t_open, t_close)
    log(f"window: {len(window_compiles)} compiles ({sum(window_compiles):.3f} s)")
    log(f"window events: {watch.describe(t_open, t_close)}")
    watch.uninstall()
    log(f"host counters over the window: {watch.host_delta(t_open, t_close)}")

    mem_peak = max(d.memory_stats()["peak_bytes_in_use"] if d.memory_stats() else 0
                   for d in devs)
    server = dict(gateway.requests)
    due = e2e.due_in(sent, t_open, t_close)
    missing = [s for s in due if e2e.missing(s)]
    late = [s.t_send - s.t_due for s in due if s.t_send]
    backlog = sum(1 for s in due if not s.t_first or s.t_first >= t_close)
    log(f"requests: {len(sent)} sent, {len(due)} due in the window, {len(missing)} missing, "
        f"{backlog} without a first token at the close; "
        f"generator late p99 {e2e.percentile(late, 99) * 1e3 if late else 0:.3f} ms, "
        f"max {max(late, default=0) * 1e3:.3f} ms")
    steps = {r.replica_id: [s for s in r.step_records() if t_open <= s.t0 < t_close]
             for r in replicas}
    ends = [sum(st[i].queue_depth for st in steps.values() if st) for i in (0, -1)]
    log(f"engine queue: {ends[0]} waiting at the window's first step, {ends[1]} at its last")
    third = (t_close - t_open) / 3
    for name, lo in (("first", t_open), ("last", t_close - third)):
        part = [s for st in steps.values() for s in st if lo <= s.t0 < lo + third]
        if part:
            log(f"{name} third of the window: mean queue {np.mean([s.queue_depth for s in part]):.2f}, "
                f"mean occupancy {np.mean([s.occupancy for s in part]):.2f}")
    slow = sorted((s for st in steps.values() for s in st), key=lambda s: s.t0 - s.t1)[:6]
    log("slowest steps (ms, prefill rows, decode rows, admitted, preempted, cow pages, "
        "s into the window): "
        + ", ".join(f"({(s.t1 - s.t0) * 1e3:.1f}, {s.prefill_rows}, {s.decode_rows}, "
                    f"{s.n_admitted}, {s.preemptions}, {s.cow_pages}, {s.t0 - t_open:.2f})"
                    for s in slow))
    for s in slow:
        if s.t1 - s.t0 > STALL_S:
            log(f"host counters over the {(s.t1 - s.t0) * 1e3:.1f}-ms step "
                f"{s.t0 - t_open:.2f} s into the window: {watch.host_delta(s.t0, s.t1)}")
    counters = ("preemptions", "evicted_pages", "cow_copies", "prefix_cached_tokens",
                "prefill_tokens", "decode_tokens")
    log("engine counters over the window: " + ", ".join(
        f"{k} {sum(snaps['close'][r][k] - snaps['open'][r][k] for r in snaps['open']):.0f}"
        for k in counters))
    delivered = sorted(t for s in sent for t in s.token_times if t_open <= t < t_close)
    pauses = np.diff([t_open] + delivered + [t_close])
    i = int(pauses.argmax())
    log(f"token deliveries: longest pause {pauses[i]:.3f} s from {np.sum(pauses[:i]):.2f} s "
        f"into the window, {int(np.sum(pauses > 0.5))} pauses over 0.5 s")
    record = RunRecord(cell=cell, cfg=cfg, arch=arch, widths=arch.widths(cfg), chips=chips,
                       t_open=t_open, t_close=t_close, sent=sent, server=server, steps=steps,
                       stats_open=snaps["open"], stats_close=snaps["close"], peak=peak,
                       plans=plans)

    # ---- the comparison, after the program's state is freed
    replica_of = {rid: getattr(req, "replica_id", "") for rid, req in server.items()}
    sample = check.pick(check.finished(sent), replica_of, seed)
    for r in replicas:
        r.engine.cache = None
    del replicas, gateway
    weights = weights[:1]
    gc.collect()
    t_ref = time.perf_counter()
    numbers: Dict[str, float] = {}
    served: Dict[str, float] = {}
    if sample:
        seqs, keep = check.sequences(sample)
        ref_logits = ref.forward(weights[0], cfg, seqs, keep, margins=control)
        if control:
            ref_logits, margins = ref_logits
        gaps = check.served_gaps(ref_logits, sample)
        numbers = check.numbers(gaps)
        log(f"served gaps: {check.describe(gaps)}")
        log(f"served numbers: {json.dumps(numbers)}")
        if control:
            ctl = check.control_gaps(ref_logits, ref.forward(weights[0], cfg, seqs, keep,
                                                             quant="fp8"))
            log(f"control gaps: {check.describe(ctl)}")
            log(f"routing near-ties: {check.near_ties(gaps, np.concatenate(margins))}")
            # the control's first-ranked tokens are judged in the served tokens' place
            served, numbers = numbers, check.numbers(ctl)
            log(f"control numbers: {json.dumps(numbers)}")
    n_checked = sum(len(s.tokens) for s in sample)
    log(f"reference: {len(sample)} requests, {n_checked} served tokens "
        f"({sum(len(s.job.prompt) for s in sample)} prompt tokens; turns "
        f"{sorted(s.job.tag for s in sample)}), {time.perf_counter() - t_ref:.2f} s")

    # ---- metrics
    out_metrics: Dict[str, dict] = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
              "memory_peak_bytes": int(mem_peak)}
    result: Dict[str, object] = {}
    if trace:
        red = {}
        if profiler.error is not None:
            log(f"profiler: {profiler.error!r}")
        elif profiler.path():
            from bench import trace as tr
            red = tr.summarize(tr.load(profiler.path()), chips, SPAN_STEP)
            if red:
                log(f"trace: {len(red['steps'])} steps in {red['window_s']:.4f} s, device busy "
                    f"{red['busy_s']:.4f} s, of it compute {red['compute_busy_s']:.4f} s")
        shutil.rmtree(profiler.dir, ignore_errors=True)
        record.trace = red
        for m in registry.metrics_of(bench, "per_layer", cell["name"]):
            v = registry.metric_reader(root, m["name"]).read(record)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red:
            device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
            result["breakdown"] = {"device_ops": [list(x) for x in red["device_ops"]],
                                   "idle_gaps": [list(x) for x in red["idle_gaps"]]}
    else:
        measured = e2e.end_to_end(sent, t_open, t_close, t_close + MAX_WAIT_S - t_open)
        measured["setup_s"] = setup_s
        for m in registry.metrics_of(bench, "end_to_end", cell["name"]):
            if m["name"] in measured:
                out_metrics[m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}
    log(f"metrics: setup_s {setup_s!r}; "
        + "; ".join(f"{k} {v['value']!r}" for k, v in out_metrics.items() if k != "setup_s"))

    checks = {k: {"value": numbers.get(k), "limit": float(v)} for k, v in limits.items()}
    checks["missing_requests"] = {"value": len(missing), "limit": 0}
    checks["checked_tokens"] = {"value": n_checked, "limit": 1}
    correct = (bool(numbers) and not missing and n_checked >= 1
               and all(numbers[k] <= float(v) for k, v in limits.items()))
    if control:
        result["served"] = served
    result = {"correct": bool(correct), "attempted": len(due), "failed": len(missing),
              "metrics": out_metrics, "device": device, **result, "check": checks}
    log(f"host peak rss {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
    for k, v in checks.items():
        at_least = " (at least)" if k == "checked_tokens" else ""
        log(f"check {k} {v['value']!r} limit{at_least} {v['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import registry
    bench = registry.load(ROOT)
    cell = registry.cell(bench, args.workload)
    try:
        import jax
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        log(f"bench: run it from a checkout of the repository ({e})")
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 1
    result = run_cell(ROOT, bench, cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices, control=bool(args.control))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
