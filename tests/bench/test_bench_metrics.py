"""Each per-layer metric's reader on a small finished run made by hand: the
numbers it reads, and nothing where the run holds nothing to read."""
import json
from pathlib import Path

import pytest

from bench import e2e, flops, registry
from bench.arch import moe_decoder as arch
from bench.client import Job, Sent
from bench.run import MAX_WAIT_S, RunRecord
from repro.core.metrics import Request
from repro.core.timeline import StepRecord

REPO = Path(__file__).resolve().parents[2]
PEAK = {"flops_bf16": 197e12, "hbm_bw": 819e9}


def _step(n, t0, prefill_rows, prefill_tokens, decode_rows):
    return StepRecord(step=n, t0=t0, t1=t0 + 0.1, budget=576,
                      tokens_packed=prefill_tokens + decode_rows, n_admitted=0,
                      prefill_rows=prefill_rows, prefill_tokens=prefill_tokens,
                      decode_rows=decode_rows, decode_tokens=decode_rows, drafted_tokens=0,
                      accepted_tokens=0, occupancy=decode_rows, max_slots=64, queue_depth=0,
                      kv_free_pages=0, kv_total_pages=3072, preemptions=0, cow_pages=0)


def _run(trace=True):
    cfg = json.loads((REPO / "bench" / "configs" / "mixtral-8x7b-pp8.json").read_text())
    sent, server = [], {}
    for i in range(10):
        s = Sent(f"c{i}", i, Job([1] * 100, 4, f"q{i}"), t_due=10.0 + i)
        sent.append(s)
        req = Request(f"c{i}", [1] * 100, max_new_tokens=4)
        req.t1, req.t2, req.replica_id = s.t_due, s.t_due + 0.01 * (i + 1), "r0"
        server[s.rid] = req
    steps = {"r0": [_step(1, 12.0, 1, 256, 32), _step(2, 12.1, 0, 0, 64)]}
    plans = {("r0", 1): {"prefill": [(0, 256)], "decode": list(range(100, 132))},
             ("r0", 2): {"prefill": [], "decode": list(range(200, 264))}}
    red = None
    if trace:
        red = {"window_s": 0.2, "busy_s": 0.15, "steps": [("r0", 1), ("r0", 2)],
               "kernel_s": {"gmm_pallas": 0.05, "chunked_prefill_pallas": 0.02},
               "device_ops": [], "idle_gaps": []}
    return RunRecord(cell={"name": "c"}, cfg=cfg, arch=arch, widths=arch.widths(cfg), chips=1,
                     t_open=10.0, t_close=20.0, sent=sent, server=server, steps=steps,
                     stats_open={"r0": {"prefix_cached_tokens": 100.0}},
                     stats_close={"r0": {"prefix_cached_tokens": 350.0}},
                     peak=PEAK, trace=red, plans=plans)


def _read(name, run):
    return registry.metric_reader(REPO, name).read(run)


def test_counter_and_span_metrics():
    run = _run()
    assert _read("decode_fill", run) == pytest.approx(100 * (32 / 64 + 64 / 64) / 2)
    assert _read("queue_wait_p90_s", run) == pytest.approx(0.09)
    assert _read("prefix_hit_share", run) == pytest.approx(100 * 250 / 1000)
    assert _read("iter_ms", run) == pytest.approx(100.0)
    assert _read("idle_share", run) == pytest.approx(25.0)
    assert _read("replica_spread", run) is None          # one chip: no router spread


def test_first_token_p90_reads_what_ttft_p90_read():
    run = _run(trace=False)
    for i, s in enumerate(run.sent[:-1]):
        s.t_first = s.t_due + 0.1 * (i + 1)          # the last one never gets a token
    want = e2e.end_to_end(run.sent, run.t_open, run.t_close,
                          run.t_close + MAX_WAIT_S - run.t_open)["ttft_p90_s"]
    assert _read("first_token_p90_s", run) == pytest.approx(want)
    assert want == pytest.approx(0.9)                 # 9th of 10: the missing one is 10th
    run.sent = []
    assert _read("first_token_p90_s", run) is None


def test_device_metrics_are_lower_bounds_over_the_traced_steps():
    run = _run()
    w = run.widths
    gmm = 4 * sum(flops.least_time(*arch.gmm_work(w, t), PEAK) for t in (256, 32, 64))
    assert _read("gmm_roofline", run) == pytest.approx(100 * gmm / 0.05)
    attn = 4 * (flops.least_time(*arch.attn_work(w, [(0, 256)]), PEAK)
                + flops.least_time(*arch.attn_work(w, [(c, 1) for c in range(100, 132)]), PEAK)
                + flops.least_time(*arch.attn_work(w, [(c, 1) for c in range(200, 264)]), PEAK))
    assert _read("attn_roofline", run) == pytest.approx(100 * attn / 0.02)
    model = (arch.model_flops(w, 256 + 32, 33, [(0, 256)] + [(c, 1) for c in range(100, 132)])
             + arch.model_flops(w, 64, 64, [(c, 1) for c in range(200, 264)]))
    assert _read("step_mfu", run) == pytest.approx(100 * model / 0.2 / 197e12)


@pytest.mark.parametrize("name", ["gmm_roofline", "attn_roofline", "step_mfu", "iter_ms",
                                  "idle_share"])
def test_trace_metrics_read_nothing_without_a_trace(name):
    assert _read(name, _run(trace=False)) is None
