"""Multi-turn sessions over a document, closed loop.

A session takes one document and asks ``turns`` questions. The prompt of
turn t is the document, the questions and served answers of turns 1..t-1,
and question t; so turns 2.. share their whole history with the turn
before, which the prefix cache can serve. After the last turn, or a failed
one, the client starts a new session.

Every seed serves the same sessions (document, question and answer
lengths) in the same order, drawn from a fixed stream; the seed chooses the
token ids alone.

Parameters: ``clients``; ``document``, ``question``, ``answer``: [min, max]
token counts, uniform; ``turns``; ``pool`` (distinct sessions drawn).
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.client import Job

SIZES_SEED = 0x5EED0002


def session_lengths(p: dict) -> List[tuple]:
    """(document length, [(question, answer) per turn]) per session, the
    same for every seed."""
    rng = np.random.default_rng(SIZES_SEED)

    def draw(lo_hi, n=None):
        return rng.integers(lo_hi[0], lo_hi[1] + 1, n)

    return [(int(draw(p["document"])),
             list(zip(draw(p["question"], p["turns"]).tolist(),
                      draw(p["answer"], p["turns"]).tolist())))
            for _ in range(p["pool"])]


class SessionSource:
    loop = "closed"

    def __init__(self, p: dict, seed: int, vocab: int, horizon_s: float):
        if p["loop"] != "closed":
            raise ValueError("sessions run closed loop")
        sizes = session_lengths(p)
        rng = np.random.default_rng(seed)
        self.sessions = []
        for doc, turns in sizes:
            self.sessions.append((
                rng.integers(1, vocab, doc).astype(np.int32),
                [(rng.integers(1, vocab, q).astype(np.int32), a) for q, a in turns]))
        self.clients = p["clients"]
        self.stagger_s = 0.5 * p.get("ramp_s", 0.0) / self.clients
        self._state = {}                      # client -> (session index, next turn)

    def next(self, client: int, last) -> Job | None:
        s, t = self._state.get(client, (client - self.clients, 0))
        doc_turns = self.sessions[s][1] if s >= 0 else []
        if last is None or last.error or not last.finished or t >= len(doc_turns):
            s, t = s + self.clients, 0        # a new session
            if s >= len(self.sessions):
                return None
            history = self.sessions[s][0]
        else:
            history = np.concatenate([last.job.prompt,
                                      np.asarray(last.tokens, np.int32)])
        question, answer = self.sessions[s][1][t]
        self._state[client] = (s, t + 1)
        return Job(np.concatenate([history, question]), answer, f"s{s}.t{t + 1}")


def make(p: dict, seed: int, vocab: int, horizon_s: float) -> SessionSource:
    return SessionSource(p, seed, vocab, horizon_s)
