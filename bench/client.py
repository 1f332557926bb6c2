"""The benchmark's client: streams a traffic ``Source`` through
``Gateway.handle`` and records, per request, what its user saw.

Derived from ``repro.core.client.run_workload`` with two changes:

* open loop: a request is timed from when it was *due*
  (``t_start + offset``), so a stall that delays later sends counts
  against them; how late each send ran is kept apart (``t_send - t_due``);
* closed loop: ``Source.next(client, last)`` gives a client its next
  request from the one that just ended, so sessions can carry their history.

A ``Source`` has ``loop`` ("closed" or "open"), and either ``clients``,
``stagger_s`` and ``next(client, last) -> Job | None`` (closed) or
``arrivals`` (open): ``(offset_s, Job)`` pairs sorted by offset.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional

import numpy as np


def now() -> float:
    return time.perf_counter()


class Job(NamedTuple):
    prompt: np.ndarray      # (S,) int32
    max_new: int
    tag: str                # e.g. "s3.t2": session 3, turn 2


@dataclass
class Sent:
    """One request as its client saw it (host clock, ``now()``)."""
    rid: str
    client: int
    job: Job
    t_due: float                       # closed loop: the send time
    t_send: float = 0.0
    t_first: float = 0.0               # 0: no token arrived
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    finished: bool = False
    error: Optional[str] = None


async def _stream(gateway, codec, s: Sent) -> None:
    s.t_send = now()
    raw = codec.encode_request(s.rid, s.job.prompt.tolist(),
                               {"max_new_tokens": s.job.max_new})
    q: "asyncio.Queue[bytes]" = asyncio.Queue()
    await gateway.handle(raw, q)
    while True:
        data = await q.get()
        if data == b"":
            s.error = "rejected"
            return
        _, token, _, fin = codec.decode_token(data)
        t = now()
        if token >= 0:                  # < 0: terminal event with no token
            if not s.tokens:
                s.t_first = t
            s.tokens.append(token)
            s.token_times.append(t)
        if fin:
            s.finished = True
            if not s.tokens:
                s.error = "no tokens"
            return


async def drive(gateway, source, *, t_start: float, t_close: float, max_wait_s: float,
                sent: Optional[List[Sent]] = None,
                stop: Optional[asyncio.Event] = None) -> List[Sent]:
    """Run ``source`` from ``t_start`` until ``t_close``, or until ``stop``
    is set; then wait, at most ``max_wait_s``, for the first token of every
    request due before the close, and cancel what is still streaming.
    Returns every request sent, in send order, in ``sent`` where given (so
    that the caller can watch it grow)."""
    from repro.core.serde import CODECS

    codec = CODECS[gateway.cfg.codec]
    sent = [] if sent is None else sent
    tasks: List[asyncio.Task] = []

    def open_() -> bool:
        return now() < t_close and not (stop is not None and stop.is_set())

    async def closed_client(i: int) -> None:
        await asyncio.sleep(max(0.0, t_start + i * source.stagger_s - now()))
        last = None
        while open_():
            job = source.next(i, last)
            if job is None:
                return
            s = Sent(f"c{i}-{len(sent)}", i, job, t_due=now())
            sent.append(s)
            await _stream(gateway, codec, s)
            last = s

    async def open_loop() -> None:
        for j, (offset, job) in enumerate(source.arrivals):
            due = t_start + offset
            if due >= t_close:
                return
            await asyncio.sleep(max(0.0, due - now()))
            if not open_():
                return
            s = Sent(f"o{j}", 0, job, t_due=due)
            sent.append(s)
            tasks.append(asyncio.ensure_future(_stream(gateway, codec, s)))

    if source.loop == "closed":
        tasks.extend(asyncio.ensure_future(closed_client(i)) for i in range(source.clients))
    else:
        tasks.append(asyncio.ensure_future(open_loop()))
    if stop is None:
        await asyncio.sleep(max(0.0, t_close - now()))
    else:
        try:
            await asyncio.wait_for(stop.wait(), max(0.0, t_close - now()))
        except asyncio.TimeoutError:
            pass
    t_end = min(now(), t_close)
    deadline = t_end + max_wait_s
    while now() < deadline and any(not s.t_first and s.error is None
                                   for s in sent if s.t_due < t_end):
        await asyncio.sleep(0.02)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return sent
