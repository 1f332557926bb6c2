"""What the process did besides serving, on the host clock: JAX's compile
path (traces, lowerings, backend compiles, persistent-cache reads),
Python's garbage collections, and the host's own counters (CPU time stolen
or spent waiting on I/O, page faults, memory compaction), sampled every
``HOST_EVERY_S``. A run counts what fell inside its window, or inside one
step, so that a stall from a program lowered again, a long collection or a
host that paused the process shows in its log beside the slowest steps.
"""
from __future__ import annotations

import gc
import threading
import time
from collections import Counter
from typing import Dict, List, Optional, Tuple

# JAX's duration events, by the short name the log gives them
DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
             "/jax/core/compile/backend_compile_duration": "compile",
             "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read"}
COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hit",
          "/jax/compilation_cache/cache_misses": "cache_miss"}

HOST_EVERY_S = 0.1
# /proc/stat's first line: jiffies of the whole host, by column
CPU_COLUMNS = {"iowait": 5, "steal": 8}
VMSTAT = ("pgmajfault", "compact_stall", "allocstall_normal", "allocstall_movable")


def host_counters() -> Dict[str, int]:
    """The host's cumulative counters now; those it cannot read are left out."""
    out: Dict[str, int] = {}
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        out.update({k: int(cpu[i]) for k, i in CPU_COLUMNS.items() if i < len(cpu)})
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        out["self_majflt"] = int(fields[9])             # field 12 of proc(5)
        with open("/proc/vmstat") as f:
            for line in f:
                k, v = line.split()
                if k in VMSTAT:
                    out[k] = int(v)
    except (OSError, ValueError, IndexError):
        pass
    return out


class Watch:
    """Records ``(t, kind, seconds, name)``: ``kind`` is one of the short
    names above or ``gc<generation>``; ``t`` is when the event ended."""

    def __init__(self):
        self.events: List[Tuple[float, str, float, str]] = []
        self.host: List[Tuple[float, Dict[str, int]]] = []
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._sampler = threading.Thread(target=self._sample, name="watch-host", daemon=True)

    def install(self) -> "Watch":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)
        gc.callbacks.append(self._gc)
        self._sample_host()
        self._sampler.start()
        return self

    def uninstall(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._count)
        gc.callbacks.remove(self._gc)
        self._stop.set()
        self._sampler.join()
        self._sample_host()

    def _sample_host(self) -> None:
        self.host.append((time.perf_counter(), host_counters()))

    def _sample(self) -> None:
        while not self._stop.wait(HOST_EVERY_S):
            self._sample_host()

    def _duration(self, event, secs, fun_name="", **_):
        if event in DURATIONS:
            self.events.append((time.perf_counter(), DURATIONS[event], secs, str(fun_name)))

    def _count(self, event, **_):
        if event in COUNTS:
            self.events.append((time.perf_counter(), COUNTS[event], 0.0, ""))

    def _gc(self, phase, info):
        t = time.perf_counter()
        if phase == "start":
            self._gc_t0 = t
        elif self._gc_t0:
            self.events.append((t, f"gc{info['generation']}", t - self._gc_t0, ""))
            self._gc_t0 = 0.0

    def compile_seconds(self, lo: float = 0.0, hi: float = float("inf")) -> List[float]:
        return [s for t, k, s, _ in self.events if k == "compile" and lo <= t < hi]

    def describe(self, lo: float, hi: float) -> str:
        """Counts, seconds and the longest of each kind in [lo, hi), and
        the functions JAX traced or lowered there."""
        inside = [e for e in self.events if lo <= e[0] < hi]
        n = Counter(k for _, k, _, _ in inside)
        parts = []
        for kind in sorted(n):
            secs = [s for _, k, s, _ in inside if k == kind]
            parts.append(f"{kind} {n[kind]} ({sum(secs):.3f} s, longest {max(secs):.3f} s)")
        names = sorted({f for _, k, _, f in inside if k in ("trace", "lower") and f})
        return "; ".join(parts or ["none"]) + (f"; traced or lowered: {names}" if names else "")

    def host_delta(self, lo: float, hi: float) -> Optional[Dict[str, int]]:
        """The host counters' growth from the last sample at or before
        ``lo`` to the first at or after ``hi``; None without such samples."""
        before = [c for t, c in self.host if t <= lo]
        after = [c for t, c in self.host if t >= hi]
        if not before or not after:
            return None
        a, b = before[-1], after[0]
        return {k: b[k] - a[k] for k in a if k in b}
