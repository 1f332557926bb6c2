"""The serving path's own measurement: named device programs, profiler spans
of the engine step and the replica's loop, the experts counter and the
row work each StepRecord carries, and the tracer's clock offset."""
import gc
import glob
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.core import EngineConfig, InferenceEngine, MetricsSink, Replica, Tracer
from repro.core.metrics import Request, now
from repro.core.observability import install_gc_spans, span
from repro.models import RunCtx, build_model
from repro.models import moe as moe_lib


@pytest.fixture(scope="module")
def moe_stack():
    cfg = tiny_config("deepseek-moe-16b")          # a dense layer, then two MoE layers
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, **over):
    kw = dict(max_slots=3, page_size=8, num_pages=64, max_seq=64,
              prefill_bucket=8, greedy=True)
    kw.update(over)
    return InferenceEngine(model, params, EngineConfig(**kw))


def _reqs(cfg, lengths, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(req_id=f"q{i}", prompt_tokens=rng.integers(1, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=max_new) for i, n in enumerate(lengths)]


def _profile(tmp_path, fn):
    """Run ``fn`` under the profiler; (host events by thread line, the
    trace's ``profile_start_time``)."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    lines, start = [], None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            lines += [[(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                       for e in line.events] for line in plane.lines]
        elif plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
    return lines, start


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_step_spans_nest_under_engine_step(moe_stack, tmp_path):
    cfg, model, params = moe_stack
    eng = _engine(model, params)
    eng.generate(_reqs(cfg, [12], max_new=2))             # compile outside the trace
    rep = Replica("r7", eng).start()
    done = threading.Event()
    reqs = _reqs(cfg, [12, 5], max_new=3, seed=1)

    def serve():
        left = {r.req_id for r in reqs}

        def on_event(ev):
            if ev.finished:
                left.discard(ev.request.req_id)
                if not left:
                    done.set()
        for r in reqs:
            rep.submit(r, on_event)
        assert done.wait(60)

    try:
        lines, _ = _profile(tmp_path, serve)
    finally:
        rep.stop()
    (line,) = [ln for ln in lines if any(e[0] == "engine.step" for e in ln)]
    steps = [e for e in line if e[0] == "engine.step"]
    assert {e[3]["replica"] for e in steps} == {"r7"}
    assert all({"step", "prefill_rows", "decode_rows"} <= set(e[3]) for e in steps)
    assert sum(e[3]["prefill_rows"] for e in steps) >= 2
    names = {e[0] for e in line}
    assert {"engine.plan", "engine.prefill.pack", "engine.prefill.run", "engine.prefill.fetch",
            "engine.decode.pack", "engine.decode.run", "engine.decode.fetch",
            "replica.drain", "replica.deliver"} <= names
    for e in line:
        if e[0].startswith("engine.") and e[0] != "engine.step":
            assert any(_inside(e, s) for s in steps), e
        if e[0].startswith("replica."):
            assert not any(_inside(e, s) for s in steps), e   # between the steps
    delivered = [e for e in line if e[0] == "replica.deliver"]
    assert sum(e[3]["events"] for e in delivered) == sum(r.n_generated for r in reqs)
    # the benchmark finds steps by its own names; no program span takes them
    assert not names & {"engine_step", "plan_iteration", "gateway_handle"}
    # each record carries the replica's delivery time of its step
    recs = [r for r in eng.step_records if r.step in {e[3]["step"] for e in steps}]
    assert recs and all(r.deliver_s > 0 for r in recs)


def test_gc_span_carries_its_generation(tmp_path):
    install_gc_spans()
    install_gc_spans()                                    # once per process
    lines, _ = _profile(tmp_path, lambda: gc.collect(2))
    found = [e for ln in lines for e in ln if e[0] == "gc"]
    assert len(found) == 1 and found[0][3] == {"generation": 2}


def test_span_costs_nothing_without_a_profiler():
    with span("engine.step", step=1) as sp:
        sp.set_metadata(rows=2)                          # a no-op, not an error
    assert not jax.profiler.TraceAnnotation.is_enabled()


def test_lowered_programs_carry_distinct_names(moe_stack):
    cfg, model, params = moe_stack
    eng = _engine(model, params)
    lowered = eng.lower_steps()
    assert set(lowered) == {"prefill", "decode"}
    assert "module @jit_prefill_step" in lowered["prefill"].as_text()
    assert "module @jit_decode_step" in lowered["decode"].as_text()
    eng_spec = _engine(model, params, enable_speculative=True, spec_k=2)
    assert "module @jit_spec_step_3" in eng_spec._spec_jit_for(3).lower(
        eng_spec.params, eng_spec.cache, *eng_spec._put((
            np.zeros((3, 3), np.int32), np.zeros(3, np.int32), np.zeros(3, np.int32),
            np.arange(3, dtype=np.int32), np.zeros(3, bool), eng_spec.page_table)),
        eng_spec._key).as_text()
    cow = eng._cow_jit.lower(eng.cache, *eng._put((np.zeros(8, np.int32),) * 2))
    assert "module @jit_cow_copy" in cow.as_text()


def test_experts_counter_recounts_the_routing_of_live_tokens(moe_stack, monkeypatch):
    cfg, model, params = moe_stack
    seen = []
    route = moe_lib.router_topk

    def recording(xf, router_w, k, norm=True):
        topw, topi, aux = route(xf, router_w, k, norm)
        jax.debug.callback(lambda t: seen.append(np.asarray(t)), topi, ordered=True)
        return topw, topi, aux

    monkeypatch.setattr(moe_lib, "router_topk", recording)
    B, C, P = 3, 8, 8
    eng = _engine(model, params, max_slots=B)
    tokens = np.random.default_rng(3).integers(1, cfg.vocab, (B, C)).astype(np.int32)
    nvalid = np.array([5, 1, 0], np.int32)                # a short row and a padding row
    pt = np.zeros((B, P), np.int32)
    pt[0, 0], pt[1, 0] = 1, 2
    ctx = RunCtx(moe_strategy="dropless")
    counters = {}
    model.decode_chunk(
        params, jnp.asarray(tokens), eng.cache, jnp.zeros(B, jnp.int32), jnp.asarray(nvalid),
        jnp.arange(B, dtype=jnp.int32), jnp.ones(B, bool), ctx, jnp.asarray(pt),
        counters=counters)
    jax.effects_barrier()
    experts = np.asarray(counters["experts"])
    assert experts.shape == (2,) and len(seen) == 2       # the MoE layers, in order
    live = (np.arange(C)[None, :] < nvalid[:, None]).reshape(-1)
    for got, topi in zip(experts, seen):
        assert got == len(np.unique(topi[live]))
    assert any(len(np.unique(t)) > g for g, t in zip(experts, seen))   # padding reaches more

    seen.clear()
    one = np.array([1, 0, 0], np.int32)                   # one live token: exactly k experts
    model.decode_chunk(
        params, jnp.asarray(tokens), eng.cache, jnp.zeros(B, jnp.int32), jnp.asarray(one),
        jnp.arange(B, dtype=jnp.int32), jnp.ones(B, bool), ctx, jnp.asarray(pt),
        counters=counters)
    assert np.asarray(counters["experts"]).tolist() == [cfg.moe.top_k] * 2


def test_experts_reached_masks_dead_tokens():
    topi = jnp.array([[0, 1], [2, 3], [1, 0]], jnp.int32)
    assert int(moe_lib.experts_reached(topi, 6)) == 4
    assert int(moe_lib.experts_reached(topi, 6, jnp.array([True, False, True]))) == 2
    assert int(moe_lib.experts_reached(topi, 6, jnp.zeros(3, bool))) == 0


def test_step_records_carry_the_work_that_ran(moe_stack):
    cfg, model, params = moe_stack
    eng = _engine(model, params, max_slots=2)
    reqs = _reqs(cfg, [12, 5], max_new=4, seed=2)
    eng.generate(reqs)
    recs = list(eng.step_records)
    n_moe, E, k = 2, cfg.moe.num_experts, cfg.moe.top_k
    fed = {}                                              # prompt tokens fed, by start
    for rec in recs:
        assert len(rec.prefill_work) == rec.prefill_rows
        assert sum(n for _, n in rec.prefill_work) == rec.prefill_tokens
        for start, n in rec.prefill_work:
            fed.setdefault(start, []).append(n)
        assert len(rec.decode_ctx) == rec.decode_rows
        for rows, reached in ((rec.prefill_rows, rec.prefill_experts),
                              (rec.decode_rows, rec.decode_experts)):
            if rows:
                assert len(reached) == n_moe and all(k <= e <= E for e in reached)
            else:
                assert reached == ()
    # chunks of 8: the 12-token prompt feeds [0, 8) then [8, 12), the 5-token one [0, 5)
    assert sorted(fed[0]) == [5, 8] and fed[8] == [4]
    # every decode row's context is the tokens resident before it: prompt + generated - 1
    ctxs = sorted(c for rec in recs for c in rec.decode_ctx)
    want = sorted(len(r.prompt_tokens) + i for r in reqs for i in range(r.n_generated - 1))
    assert ctxs == want


def test_tracer_spans_lay_over_the_profiler_clock(tmp_path):
    tracer = Tracer()

    def both():
        with jax.profiler.TraceAnnotation("probe"):
            t0 = now()
            tracer.add("r", "probe", t0, t0)

    lines, start = _profile(tmp_path, both)
    (probe,) = [e for ln in lines for e in ln if e[0] == "probe"]
    (s,) = tracer.pop("r")
    on_profiler = s.t0 * 1e9 + tracer.clock_offset_ns - start
    assert abs(on_profiler - probe[1]) < 1e6                # within 1 ms


def test_record_trace_exports_the_clock_offset(tmp_path):
    path = tmp_path / "t.jsonl"
    sink = MetricsSink(str(path))
    r = Request(req_id="a", prompt_tokens=np.arange(3, dtype=np.int32))
    sink.record_trace(r, [], clock_offset_ns=123)
    sink.close()
    assert json.loads(path.read_text())["clock_offset_ns"] == 123
