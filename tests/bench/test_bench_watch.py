"""The run's watch: JAX's compile path, collections and the host's counters,
counted by window."""
import gc
import time

import jax
import jax.numpy as jnp

from bench import watch as W


def test_host_counters_read_and_delta_brackets_the_interval():
    c = W.host_counters()
    assert {"iowait", "steal", "self_majflt"} <= set(c)
    assert all(isinstance(v, int) and v >= 0 for v in c.values())

    w = W.Watch()
    w.host = [(1.0, {"steal": 10, "pgmajfault": 5}), (2.0, {"steal": 12, "pgmajfault": 5}),
              (3.0, {"steal": 20, "pgmajfault": 9})]
    assert w.host_delta(1.5, 2.5) == {"steal": 10, "pgmajfault": 4}     # samples 1.0 -> 3.0
    assert w.host_delta(2.0, 2.0) == {"steal": 0, "pgmajfault": 0}
    assert w.host_delta(0.5, 2.5) is None and w.host_delta(1.5, 3.5) is None


def test_watch_counts_compiles_and_collections_inside_a_window_and_stops():
    x = jnp.arange(7.0).block_until_ready()
    w = W.Watch().install()
    try:
        t0 = time.perf_counter()
        jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
        gc.collect()
        time.sleep(2.5 * W.HOST_EVERY_S)
        t1 = time.perf_counter()
    finally:
        w.uninstall()
    assert not w._sampler.is_alive()
    assert len(w.compile_seconds(t0, t1)) == 1 and w.compile_seconds(t1) == []
    text = w.describe(t0, t1)
    assert "compile 1" in text and "gc2 1" in text and "trace" in text
    assert w.describe(t1 + 1, t1 + 2) == "none"
    assert w.host_delta(t0, t1) is not None
