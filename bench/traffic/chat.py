"""Chat traffic: OpenOrca-shaped prompt and answer lengths, closed or open
loop. Derived from ``repro.data.workload`` (lognormal prompts, geometric
answers, Poisson arrivals with periodic bursts).

Every seed serves the same lengths in the same order and, in open loop, at
the same arrival times, all drawn from a fixed stream; the seed chooses the
token ids alone. So seeds differ in what is computed, not in how much.

Parameters: ``loop``; ``clients`` (closed); ``rate``, ``burst_mult``,
``burst_period_s``, ``burst_duty`` (open, requests/s); ``prompt``
{median, sigma, min, max}; ``output`` {mean, max}; ``pool`` (distinct
requests drawn).
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from bench.client import Job

SIZES_SEED = 0x5EED0001


def lengths(p: dict) -> Tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, answer lengths) of the pool, the same for every seed."""
    rng = np.random.default_rng(SIZES_SEED)
    n, pr, out = p["pool"], p["prompt"], p["output"]
    plen = np.exp(rng.normal(np.log(pr["median"]), pr["sigma"], n))
    plen = np.clip(np.round(plen), pr["min"], pr["max"]).astype(int)
    olen = np.minimum(rng.geometric(1.0 / out["mean"], n), out["max"]).astype(int)
    return plen, np.maximum(olen, 1)


def arrivals(p: dict, horizon_s: float) -> List[float]:
    """Offsets (s) of a Poisson process at ``rate``, multiplied by
    ``burst_mult`` for the first ``burst_duty`` of every ``burst_period_s``
    (thinning at the peak rate), up to ``horizon_s``; the same for every seed."""
    rng = np.random.default_rng(SIZES_SEED + 1)
    rate, mult = p["rate"], max(p.get("burst_mult", 1.0), 1.0)
    period, duty = p.get("burst_period_s", 10.0), p.get("burst_duty", 0.0)
    peak = rate * mult
    t, out = 0.0, []
    while True:
        t += float(rng.exponential(1.0 / peak))
        if t >= horizon_s:
            return out
        in_burst = (t % period) / period < duty
        if rng.random() < (peak if in_burst else rate) / peak:
            out.append(t)


class ChatSource:
    def __init__(self, p: dict, seed: int, vocab: int, horizon_s: float):
        plen, olen = lengths(p)
        rng = np.random.default_rng(seed)
        self.jobs = [Job(rng.integers(1, vocab, plen[j]).astype(np.int32),
                         int(olen[j]), f"q{j}") for j in range(len(plen))]
        self.loop = p["loop"]
        if self.loop == "closed":
            self.clients = p["clients"]
            # clients start spread over the first half of the ramp
            self.stagger_s = 0.5 * p.get("ramp_s", 0.0) / self.clients
            self._next = list(range(self.clients))
        else:
            offs = arrivals(p, horizon_s)
            if len(offs) > len(self.jobs):
                raise ValueError(f"{len(offs)} arrivals but a pool of {len(self.jobs)}")
            self.arrivals = list(zip(offs, self.jobs))

    def next(self, client: int, last) -> Job | None:
        j = self._next[client]
        if j >= len(self.jobs):
            return None
        self._next[client] = j + self.clients
        return self.jobs[j]


def make(p: dict, seed: int, vocab: int, horizon_s: float) -> ChatSource:
    return ChatSource(p, seed, vocab, horizon_s)
