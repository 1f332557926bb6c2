"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device op intervals, busy time, idle gaps and what the host
was doing in each, per-kernel device time, the benchmark's host spans.

Times are nanoseconds on the trace's own clock. A device is a plane named
``/device:<KIND>:<n>``; its operations are the events of its ``XLA Ops``
line and of its ``Async XLA Ops`` line (copies the device runs beside, or
instead of, compute: a step waits for them, so they count as busy). Host
spans are the events of the host plane's lines.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPS_LINES = ("XLA Ops", "Async XLA Ops")
# a Pallas kernel's calls carry a name that ends so (``<kernel>_pallas``)
KERNEL_SUFFIX = "_pallas"


@dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict = field(default_factory=dict)


@dataclass
class Trace:
    ops: Dict[int, List[Span]]            # device ordinal -> its operations
    host: List[List[Span]]                # one list per host thread (line)
    async_ops: Dict[int, List[Span]] = field(default_factory=dict)   # the async line's


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lines: Dict[str, Dict[int, List[Span]]] = {name: {} for name in OPS_LINES}
    host: List[List[Span]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            spans = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                     for e in line.events]
            if m and line.name in OPS_LINES:
                lines[line.name][int(m.group(1))] = sorted(spans, key=lambda s: s.start)
            elif plane.name.startswith("/host:"):
                host.append(spans)
    return Trace(ops=lines[OPS_LINES[0]], host=host, async_ops=lines[OPS_LINES[1]])


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def busy(ops: Sequence[Span], lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi) in which some operation ran."""
    return sum(b - a for a, b in merge(clip([(s.start, s.end) for s in ops], lo, hi)))


def gaps(ops: Sequence[Span], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi)."""
    out, t = [], lo
    for a, b in merge(clip([(s.start, s.end) for s in ops], lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_activity(host: Sequence[Sequence[Span]], t: float, names: Sequence[str]) -> str:
    """What the serving threads did at ``t``: the innermost host span at
    ``t`` on a thread that carries one of ``names`` (the benchmark's own
    spans), or "idle" where none is open."""
    best: Optional[Span] = None
    for line in host:
        if not any(s.name in names for s in line):
            continue
        for s in line:
            if s.start <= t < s.end and (best is None or s.start >= best.start):
                best = s
    return best.name if best is not None else "idle"


def base_name(op: str) -> str:
    """An op's HLO instruction name without its numeric suffix: the TPU
    trace names an op by its whole instruction (``%foo_pallas.25 = bf16[..]
    custom-call(..)``), the CPU by its name alone (``fusion.12``)."""
    name = op.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


# ops that contain others (a scan's loop): their time is their body's
CONTAINERS = ("while", "conditional", "call")


def top_ops(ops: Sequence[Span], lo: float, hi: float, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` op names that took most device time in [lo, hi), seconds."""
    total: Dict[str, float] = defaultdict(float)
    for s in ops:
        name = base_name(s.name)
        if s.start >= lo and s.end <= hi and name not in CONTAINERS:
            total[name] += s.end - s.start
    return [(k, v * 1e-9) for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def kernel_ns(ops: Sequence[Span], windows: Sequence[Interval]) -> Dict[str, float]:
    """Device nanoseconds, by kernel, of the Pallas kernels' calls that lie
    inside ``windows``."""
    windows = sorted(windows)
    starts = [a for a, _ in windows]
    out: Dict[str, float] = defaultdict(float)
    for s in ops:
        name = base_name(s.name)
        if not name.endswith(KERNEL_SUFFIX):
            continue
        i = bisect.bisect_right(starts, s.start) - 1
        if i >= 0 and s.end <= windows[i][1]:
            out[name] += s.end - s.start
    return dict(out)


def summarize(t: Trace, chips: int, step_span: str) -> dict:
    """The traced slice: the engine steps (host spans ``step_span``, with
    ``replica`` and ``step`` stats) that lie wholly in the trace; per
    device, the busy time within their extent (compute and async ops; the
    compute alone apart); the device time inside the steps of every Pallas
    kernel that ran there; the top ops; and the idle time by what the
    serving threads were doing.
    Empty where the trace holds no steps or devices."""
    steps = [(str(s.stats["replica"]), int(s.stats["step"]), s.start, s.end)
             for line in t.host for s in line if s.name == step_span and "step" in s.stats]
    if not steps or not t.ops:
        return {}
    lo, hi = min(s[2] for s in steps), max(s[3] for s in steps)
    devices = sorted(t.ops)[:chips]
    every = {d: t.ops[d] + t.async_ops.get(d, []) for d in devices}
    windows = [(a, b) for _, _, a, b in steps]
    kernels: Dict[str, float] = defaultdict(float)
    for d in devices:
        for name, ns in kernel_ns(t.ops[d], windows).items():
            kernels[name] += ns * 1e-9
    idle: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    for d in devices:
        for a, b in gaps(every[d], lo, hi):
            idle[host_activity(t.host, (a + b) / 2, (step_span,))] += (b - a) * 1e-9
        for name, secs in top_ops(every[d], lo, hi, n=50):
            ops[name] += secs

    def mean_busy(by_device):
        return sum(busy(by_device[d], lo, hi) for d in devices) / len(devices) * 1e-9

    return {"window_s": (hi - lo) * 1e-9, "busy_s": mean_busy(every),
            "compute_busy_s": mean_busy(t.ops),
            "steps": [(r, n) for r, n, _, _ in steps], "kernel_s": dict(kernels),
            "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:10]}
