"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the served path
finished is drawn from the seed: the longest of each replica first, then
others at random, until it holds ``SAMPLE_TOKENS`` served tokens or
``SAMPLE_REQUESTS`` requests. The plain reference runs once over each
prompt with its served tokens. Every served token is greedy, so the
reference's best logit at its position should be its own. Per position the
gap is how far the served token's reference logit lies below the
reference's best; ``numbers`` reduces the gaps, and a cell's limits file
(``bench/limits/<cell>.json``) names the numbers compared and their limits.

The control puts the reference in the program's place at a precision below
the served one (``quant="fp8"``): at each position of the same prompts and
tokens it reads the gap of the token that the control puts first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

SAMPLE_TOKENS = 768
SAMPLE_REQUESTS = 8


def finished(sent) -> list:
    return [s for s in sent if s.finished and s.error is None and s.tokens]


def pick(done: Sequence, replica_of: Dict[str, str], seed: int) -> list:
    """The sample: each replica's longest finished request (prompt and
    served tokens), then the longest others until half of
    ``SAMPLE_TOKENS`` is served (in a session mix these are the later
    turns, whose history came from the prefix cache), then others drawn
    from the seed."""
    size = {s.rid: len(s.job.prompt) + len(s.tokens) for s in done}
    by_size = sorted(done, key=lambda s: (-size[s.rid], s.rid))
    chosen: List = []
    for rep in sorted({replica_of.get(s.rid, "") for s in done}):
        chosen.append(next(s for s in by_size if replica_of.get(s.rid, "") == rep))

    def full(share: float) -> bool:
        return (sum(len(s.tokens) for s in chosen) >= share * SAMPLE_TOKENS
                or len(chosen) >= SAMPLE_REQUESTS)

    for s in by_size:
        if full(0.5):
            break
        if s.rid not in {c.rid for c in chosen}:
            chosen.append(s)
    taken = {c.rid for c in chosen}
    rest = [s for s in done if s.rid not in taken]
    for j in np.random.default_rng(seed).permutation(len(rest)):
        if full(1.0):
            break
        chosen.append(rest[j])
    return chosen


def sequences(sample) -> tuple:
    """(token sequences, positions kept): each prompt with its served
    tokens but the last, scored at the positions that chose them."""
    seqs = [np.concatenate([s.job.prompt, np.asarray(s.tokens[:-1], np.int32)])
            for s in sample]
    return seqs, [len(s.tokens) for s in sample]


def served_gaps(ref_logits, sample) -> np.ndarray:
    """Per sampled position: reference best minus the served token's logit."""
    out = []
    for lg, s in zip(ref_logits, sample):
        lg = np.asarray(lg, np.float32)
        out.append(lg.max(axis=1) - lg[np.arange(len(s.tokens)), s.tokens])
    return np.concatenate(out)


def control_gaps(ref_logits, control_logits) -> np.ndarray:
    """Per position: reference best minus the reference logit of the token
    the control puts first."""
    out = []
    for lg, cl in zip(ref_logits, control_logits):
        lg = np.asarray(lg, np.float32)
        top = np.asarray(cl, np.float32).argmax(axis=1)
        out.append(lg.max(axis=1) - lg[np.arange(len(top)), top])
    return np.concatenate(out)


def describe(gaps: np.ndarray) -> str:
    """How the gaps spread: positions, the share off the reference's best,
    quantiles and the widest."""
    q = np.quantile(gaps, [0.5, 0.9, 0.99])
    return (f"{len(gaps)} positions, {100 * np.mean(gaps > 0):.2f}% off the best, "
            f"{100 * np.mean(gaps > 0.1):.2f}% by > 0.1; p50 {q[0]:.4g} p90 {q[1]:.4g} "
            f"p99 {q[2]:.4g} max {gaps.max():.4g}")


def near_ties(gaps: np.ndarray, margins: np.ndarray, tie: float = 0.01) -> str:
    """How close the routing of the positions a served token missed by more
    than 0.1 came to a tie, beside that of all positions (``margins``: the
    reference's least top-k router margin per position)."""
    def part(m):
        return (f"{len(m)} positions, margin p50 {np.median(m) if len(m) else 0:.4g}, "
                f"{100 * np.mean(m < tie) if len(m) else 0:.2f}% under {tie}")
    return f"off by > 0.1: {part(margins[gaps > 0.1])}; all: {part(margins)}"


def numbers(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers a run can compare, each over every sampled position: the
    widest gap; the 90th and 95th percentile gaps; the mean gap; the
    share (percent) of positions off the reference's best, and off it by
    more than 0.1."""
    q90, q95 = np.quantile(gaps, [0.9, 0.95])
    return {"logit_gap": float(gaps.max()), "gap_p90": float(q90), "gap_p95": float(q95),
            "gap_mean": float(gaps.mean()),
            "off_best_pct": 100.0 * float(np.mean(gaps > 0)),
            "off_tenth_pct": 100.0 * float(np.mean(gaps > 0.1))}
