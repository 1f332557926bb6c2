"""Trace reduction on a small synthetic trace and on one recorded here:
busy union, idle gaps and what the host did in them, per-kernel sums."""
import pytest

from bench import trace as tr
from bench.trace import Span, Trace


def _ops(*spans):
    return [Span(n, a, b) for n, a, b in spans]


def test_busy_union_and_gaps():
    ops = _ops(("fusion.1", 0, 10), ("fusion.2", 5, 15), ("gmm_pallas.3", 20, 30))
    assert tr.busy(ops, 0, 40) == 25
    assert tr.gaps(ops, 0, 40) == [(15, 20), (30, 40)]
    assert tr.busy(ops, 8, 22) == 9 and tr.gaps(ops, 8, 22) == [(15, 20)]


def test_kernel_time_inside_step_windows_only():
    ops = _ops(("gmm_pallas", 0, 4), ("gmm_pallas.12", 10, 13), ("gmm_pallas", 30, 35),
               ("chunked_prefill_pallas.1", 11, 12), ("gmm_pallas_like", 10, 12))
    assert tr.kernel_ns(ops, [(9, 20), (29, 34)]) == {"gmm_pallas": 3,
                                                      "chunked_prefill_pallas": 1}
    assert tr.kernel_ns(ops, [(9, 20)])["chunked_prefill_pallas"] == 1
    assert tr.base_name("fusion.3.1") == "fusion"


def test_top_ops_sum_by_name():
    ops = _ops(("copy.1", 0, 5), ("copy.2", 5, 9), ("gmm_pallas", 9, 12))
    assert tr.top_ops(ops, 0, 20) == [("copy", pytest.approx(9e-9)),
                                      ("gmm_pallas", pytest.approx(3e-9))]


def _trace():
    host = [[Span("engine_step", 0, 100, {"replica": "r0", "step": 7}),
             Span("plan_iteration", 2, 10), Span("PjitFunction(_step_fn)", 40, 48),
             Span("engine_step", 110, 200, {"replica": "r0", "step": 8})],
            [Span("gateway_handle", 0, 300)]]                 # another thread
    ops = _ops(("fusion", 12, 40), ("gmm_pallas.1", 50, 90), ("chunked_prefill_pallas", 120, 150),
               ("gmm_pallas", 150, 190), ("gmm_pallas", 250, 260))  # after the last step
    return Trace(ops={0: ops}, host=host)


def test_host_activity_is_the_innermost_span_of_a_serving_thread():
    t = _trace()
    assert tr.host_activity(t.host, 5, ("engine_step",)) == "plan_iteration"
    assert tr.host_activity(t.host, 42, ("engine_step",)) == "PjitFunction(_step_fn)"
    assert tr.host_activity(t.host, 105, ("engine_step",)) == "idle"


def test_summarize_steps_busy_kernels_and_gaps():
    s = tr.summarize(_trace(), 1, "engine_step")
    assert s["steps"] == [("r0", 7), ("r0", 8)]
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["busy_s"] == pytest.approx((28 + 40 + 30 + 40) * 1e-9)
    assert s["kernel_s"]["gmm_pallas"] == pytest.approx(80e-9)
    assert s["kernel_s"]["chunked_prefill_pallas"] == pytest.approx(30e-9)
    idle = dict(s["idle_gaps"])
    # gaps: [0,12) plan, [40,50) pjit, [90,120) idle between steps mostly, [190,200)
    assert idle["plan_iteration"] == pytest.approx(12e-9)
    assert idle["PjitFunction(_step_fn)"] == pytest.approx(10e-9)
    assert sum(idle.values()) == pytest.approx((200 - 138) * 1e-9)
    assert tr.summarize(Trace(ops={}, host=_trace().host), 1, "engine_step") == {}


def test_summarize_times_every_pallas_kernel():
    t = _trace()
    t.ops[0] += _ops(("%foo_pallas.7 = bf16[8] custom-call()", 60, 75),
                     ("foo_pallas", 205, 210),                    # between steps
                     ("foo_pallas_like.1", 125, 130))
    s = tr.summarize(t, 1, "engine_step")
    assert s["kernel_s"] == {"gmm_pallas": pytest.approx(80e-9),
                             "chunked_prefill_pallas": pytest.approx(30e-9),
                             "foo_pallas": pytest.approx(15e-9)}


def test_async_ops_count_as_busy_and_compute_apart():
    t = _trace()
    t.async_ops = {0: _ops(("copy-start.3", 85, 115), ("copy-start.4", 250, 300))}
    s = tr.summarize(t, 1, "engine_step")
    assert s["compute_busy_s"] == pytest.approx((28 + 40 + 30 + 40) * 1e-9)
    assert s["busy_s"] == pytest.approx((28 + 40 + 30 + 40 + 25) * 1e-9)   # [90, 115)
    assert dict(s["device_ops"])["copy-start"] == pytest.approx(30e-9)
    assert s["kernel_s"]["gmm_pallas"] == pytest.approx(80e-9)
    assert sum(dict(s["idle_gaps"]).values()) == pytest.approx((200 - 163) * 1e-9)


def test_load_reads_a_recorded_trace(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("engine_step", replica="r0", step=3):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    t = tr.load(path)
    steps = [s for line in t.host for s in line if s.name == "engine_step"]
    assert len(steps) == 1 and steps[0].stats == {"replica": "r0", "step": 3}
    assert steps[0].end > steps[0].start


def test_tpu_op_names_and_containers():
    op = "%gmm_pallas.25 = bf16[2048,14336]{1,0} custom-call(s32[16]{0} %copy-done.28)"
    assert tr.base_name(op) == "gmm_pallas"
    ops = _ops(("%while.120 = (s32[], bf16[64,1,4096]) while(%tuple.3)", 0, 100),
               ("%gmm_pallas.1 = bf16[8] custom-call()", 10, 30),
               ("%fusion.2 = bf16[8] fusion()", 40, 50))
    assert dict(tr.top_ops(ops, 0, 100)) == {"gmm_pallas": pytest.approx(20e-9),
                                             "fusion": pytest.approx(10e-9)}
    assert tr.kernel_ns(ops, [(0, 100)]) == {"gmm_pallas": 20}
