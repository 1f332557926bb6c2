"""End-to-end metrics of one run, from what the clients saw.

* ``output_tok_s``: tokens that reached their clients inside the window,
  over the window's length;
* ``ttft_p90_s``: 90th percentile, over every request due inside the window
  (open loop: its scheduled time; closed loop: its send), of due -> first
  token at the client. A request that failed or whose first token never
  came is missing: it counts as ``missing_s``, above any real reading;
* ``itl_p99_ms``: 99th percentile over every gap between consecutive tokens
  of one request that ends inside the window.

Percentiles are nearest-rank over the whole sample: no windows, no buckets.
"""
from __future__ import annotations

import math
from typing import List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100)."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def due_in(sent, t_open: float, t_close: float) -> List:
    return [s for s in sent if t_open <= s.t_due < t_close]


def missing(s) -> bool:
    return s.error is not None or not s.t_first


def ttfts(sent, t_open: float, t_close: float, missing_s: float) -> List[float]:
    return [missing_s if missing(s) else s.t_first - s.t_due
            for s in due_in(sent, t_open, t_close)]


def gaps(sent, t_open: float, t_close: float) -> List[float]:
    return [b - a for s in sent for a, b in zip(s.token_times, s.token_times[1:])
            if t_open <= b < t_close]


def tokens_in(sent, t_open: float, t_close: float) -> int:
    return sum(1 for s in sent for t in s.token_times if t_open <= t < t_close)


def end_to_end(sent, t_open: float, t_close: float, missing_s: float) -> dict:
    seconds = t_close - t_open
    out = {"output_tok_s": tokens_in(sent, t_open, t_close) / seconds}
    t = ttfts(sent, t_open, t_close, missing_s)
    if t:
        out["ttft_p90_s"] = percentile(t, 90)
    g = gaps(sent, t_open, t_close)
    if g:
        out["itl_p99_ms"] = percentile(g, 99) * 1e3
    return out
