"""The readers of the program's own counters and spans (``gmm_expert_roofline``,
``deliver_ms``) on runs made by hand, and the trace reduction's existing
numbers with the program's spans in the trace: unchanged."""
import dataclasses
import glob
import json
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import flops, registry
from bench import trace as tr
from bench.arch import moe_decoder as arch
from bench.run import SPAN_HANDLE, SPAN_PLAN, SPAN_STEP, RunRecord, instrument
from repro.configs import tiny_config
from repro.core import EngineConfig, InferenceEngine, Replica
from repro.core.metrics import Request
from repro.core.timeline import StepRecord
from repro.models import build_model

REPO = Path(__file__).resolve().parents[2]
PEAK = {"flops_bf16": 197e12, "hbm_bw": 819e9}


def _step(n, prefill_work, decode_ctx, prefill_experts=(), decode_experts=(), deliver_s=0.0):
    tokens = sum(k for _, k in prefill_work)
    return StepRecord(step=n, t0=12.0 + n / 10, t1=12.05 + n / 10, budget=576,
                      tokens_packed=tokens + len(decode_ctx), n_admitted=0,
                      prefill_rows=len(prefill_work), prefill_tokens=tokens,
                      decode_rows=len(decode_ctx), decode_tokens=len(decode_ctx),
                      drafted_tokens=0, accepted_tokens=0, occupancy=len(decode_ctx),
                      max_slots=64, queue_depth=0, kv_free_pages=0, kv_total_pages=3072,
                      preemptions=0, cow_pages=0, prefill_work=tuple(prefill_work),
                      decode_ctx=tuple(decode_ctx), prefill_experts=prefill_experts,
                      decode_experts=decode_experts, deliver_s=deliver_s)


@dataclasses.dataclass
class _OlderStep:
    """A StepRecord of a program without the counters."""
    step: int
    prefill_rows: int = 1
    decode_rows: int = 64


def _run(steps, trace=True):
    cfg = json.loads((REPO / "bench" / "configs" / "mixtral-8x7b-pp8.json").read_text())
    red = None
    if trace:
        red = {"window_s": 0.2, "busy_s": 0.15, "steps": [("r0", s.step) for s in steps],
               "kernel_s": {"gmm_pallas": 0.05, "chunked_prefill_pallas": 0.02},
               "device_ops": [], "idle_gaps": []}
    plans = {("r0", 1): {"prefill": [(0, 256)], "decode": list(range(100, 132))},
             ("r0", 2): {"prefill": [], "decode": list(range(200, 264))}}
    return RunRecord(cell={"name": "c"}, cfg=cfg, arch=arch, widths=arch.widths(cfg), chips=1,
                     t_open=10.0, t_close=20.0, sent=[], server={}, steps={"r0": steps},
                     stats_open={}, stats_close={}, peak=PEAK, trace=red, plans=plans)


def _read(name, run):
    return registry.metric_reader(REPO, name).read(run)


def _counted():
    return [_step(1, [(0, 256)], range(100, 132), (8, 8, 8, 8), (7, 8, 6, 8), 0.004),
            _step(2, [], range(200, 264), (), (8, 8, 8, 8), 0.006)]


def test_gmm_expert_roofline_streams_every_expert_reached():
    run = _run(_counted())
    w = run.widths

    def call(tokens, reached):
        total = 0.0
        for e in reached:
            fl, nb = arch.gmm_work(w, tokens)
            total += flops.least_time(fl, nb + 3 * (e - w["k"]) * w["d"] * w["f"] * 2, PEAK)
        return total

    least = call(256, (8,) * 4) + call(32, (7, 8, 6, 8)) + call(64, (8,) * 4)
    got = _read("gmm_expert_roofline", run)
    assert got == pytest.approx(100 * least / 0.05)
    # the same calls at k experts each are gmm_roofline's lower bound
    assert got > _read("gmm_roofline", run) > 0


def test_gmm_expert_roofline_equals_gmm_roofline_at_k_experts():
    k = 2
    run = _run([_step(1, [(0, 256)], range(100, 132), (k,) * 4, (k,) * 4),
                _step(2, [], range(200, 264), (), (k,) * 4)])
    assert _read("gmm_expert_roofline", run) == pytest.approx(_read("gmm_roofline", run))


def test_deliver_ms_is_the_mean_over_traced_steps():
    run = _run(_counted())
    assert _read("deliver_ms", run) == pytest.approx(5.0)
    run.trace["steps"] = [("r0", 2)]                     # only step 2 lies in the trace
    assert _read("deliver_ms", run) == pytest.approx(6.0)


@pytest.mark.parametrize("name", ["gmm_expert_roofline", "deliver_ms"])
def test_program_metrics_read_nothing_where_nothing_is_recorded(name):
    assert _read(name, _run(_counted(), trace=False)) is None
    # a program that predates the counters: its records lack them
    assert _read(name, _run([_OlderStep(1), _OlderStep(2)])) is None
    empty = _run(_counted())
    empty.trace["steps"] = []
    assert _read(name, empty) is None


# ------------------------------------------------ the reduction, unchanged
def _recorded_host_lines(tmp_path):
    """Host lines of a profiled tiny MoE replica whose engine the benchmark
    instruments as it does on the chip: its spans and the program's."""
    cfg = tiny_config("mixtral-8x7b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(max_slots=3, page_size=8, num_pages=64,
                                                      max_seq=64, prefill_bucket=8, greedy=True))
    rng = np.random.default_rng(0)
    eng.generate([Request("w", rng.integers(1, cfg.vocab, 12).astype(np.int32), max_new_tokens=2)])
    rep = Replica("r0", eng)
    instrument(rep, {})
    rep.start()
    done = threading.Event()
    reqs = [Request(f"q{i}", rng.integers(1, cfg.vocab, n).astype(np.int32), max_new_tokens=4)
            for i, n in enumerate((12, 5))]
    left = {r.req_id for r in reqs}

    def on_event(ev):
        if ev.finished:
            left.discard(ev.request.req_id)
            if not left:
                done.set()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0                  # as the benchmark's profiler has it
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for r in reqs:
            rep.submit(r, on_event)
        assert done.wait(60)
    finally:
        jax.profiler.stop_trace()
        rep.stop()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return tr.load(path).host


def test_summarize_existing_keys_unchanged_by_program_spans(tmp_path):
    host = _recorded_host_lines(tmp_path)
    names = {s.name for line in host for s in line}
    program = {n for n in names if n.startswith(("engine.", "replica.", "gateway."))} | {"gc"}
    assert {"engine.step", "engine.decode.run", "replica.deliver"} <= names
    assert not program & {SPAN_STEP, SPAN_PLAN, SPAN_HANDLE}
    # device work where the step programs ran: from each dispatch to its fetch's end
    ops = []
    for line in host:
        runs = sorted((s for s in line if s.name.endswith((".run", ".fetch"))),
                      key=lambda s: s.start)
        for a, b in zip(runs[::2], runs[1::2]):
            mid = (a.start + b.end) / 2
            ops += [tr.Span("fusion.1", a.start, mid), tr.Span("gmm_pallas.2", mid, b.end)]
    ops.sort(key=lambda s: s.start)
    assert ops
    stripped = [[s for s in line if s.name not in program] for line in host]
    before = tr.summarize(tr.Trace(ops={0: ops}, host=stripped), 1, SPAN_STEP)
    after = tr.summarize(tr.Trace(ops={0: ops}, host=host), 1, SPAN_STEP)
    assert before["steps"]
    for key in ("steps", "window_s", "busy_s", "compute_busy_s", "kernel_s", "device_ops"):
        assert after[key] == before[key], key
    gaps_before, gaps_after = dict(before["idle_gaps"]), dict(after["idle_gaps"])
    assert sum(gaps_after.values()) == pytest.approx(sum(gaps_before.values()))
    # the gaps now name what the serving thread was doing
    assert set(gaps_after) & program
