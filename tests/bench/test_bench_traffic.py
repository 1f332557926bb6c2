"""Traffic generators: deterministic per seed, the same sizes for every
seed, and the parameters of their mix held."""
import numpy as np
import pytest

from bench.traffic import chat, sessions

CHAT = dict(kind="chat", loop="closed", clients=64, ramp_s=12, pool=4096,
            prompt=dict(median=150, sigma=0.8, min=4, max=2048), output=dict(mean=256, max=512))
OPEN = dict(CHAT, loop="open", rate=3.0, burst_mult=2.0, burst_period_s=10.0, burst_duty=0.3)
DOCQA = dict(kind="sessions", loop="closed", clients=12, ramp_s=12, pool=512,
             document=[1536, 2560], turns=4, question=[32, 128], answer=[64, 256])


def _jobs(src):
    return [(j.prompt.tolist(), j.max_new) for j in src.jobs]


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_chat_is_deterministic_per_seed(seed):
    a, b = chat.make(CHAT, seed, 32000, 60), chat.make(CHAT, seed, 32000, 60)
    assert _jobs(a) == _jobs(b)
    assert _jobs(a) != _jobs(chat.make(CHAT, seed + 1, 32000, 60))


def test_chat_seeds_share_their_sizes_and_order():
    a, b = chat.make(CHAT, 1, 32000, 60), chat.make(CHAT, 2, 32000, 60)
    assert [(len(j.prompt), j.max_new) for j in a.jobs] == \
        [(len(j.prompt), j.max_new) for j in b.jobs]
    assert not np.array_equal(a.jobs[0].prompt, b.jobs[0].prompt)


def test_chat_lengths_hold_the_mix():
    plen, olen = chat.lengths(CHAT)
    assert plen.min() >= 4 and plen.max() <= 2048 and olen.min() >= 1 and olen.max() <= 512
    assert 130 <= np.median(plen) <= 170
    assert 200 <= olen.mean() <= 260          # geometric, mean 256, capped at 512


def test_open_loop_rate_and_bursts():
    offs = np.asarray(chat.arrivals(OPEN, 2000.0))
    assert np.all(np.diff(offs) > 0) and offs.max() < 2000.0
    # mean rate: 0.7 of the time at 3/s, 0.3 at 6/s
    assert len(offs) / 2000.0 == pytest.approx(3.0 * (0.7 + 0.3 * 2.0), rel=0.05)
    burst = (offs % 10.0) < 3.0
    assert burst.sum() / 3.0 == pytest.approx(2 * (~burst).sum() / 7.0, rel=0.1)
    a, b = chat.make(OPEN, 1, 32000, 100), chat.make(OPEN, 2, 32000, 100)
    assert [t for t, _ in a.arrivals] == [t for t, _ in b.arrivals]


def test_closed_loop_clients_take_disjoint_jobs():
    src = chat.make(CHAT, 3, 32000, 60)
    first = [src.next(i, None) for i in range(64)]
    again = src.next(0, None)
    assert again is src.jobs[64] and first[5] is src.jobs[5]


class _Done:
    def __init__(self, job, tokens):
        self.job, self.tokens, self.finished, self.error = job, tokens, True, None


def test_sessions_carry_history_and_share_their_document():
    src = sessions.make(DOCQA, 9, 102400, 60)
    j1 = src.next(0, None)
    doc_len = len(j1.prompt)
    answer = list(range(1, j1.max_new + 1))
    j2 = src.next(0, _Done(j1, answer))
    assert j1.tag.endswith(".t1") and j2.tag.endswith(".t2")
    assert np.array_equal(j2.prompt[:doc_len], j1.prompt)
    assert j2.prompt[doc_len:doc_len + len(answer)].tolist() == answer
    last = j2
    for _ in range(2):
        last = src.next(0, _Done(last, [7] * last.max_new))
    assert last.tag.endswith(".t4") and len(last.prompt) <= 2560 + 4 * 128 + 3 * 256
    fresh = src.next(0, _Done(last, [7] * last.max_new))
    assert fresh.tag.endswith(".t1") and not np.array_equal(fresh.prompt[:50], j1.prompt[:50])


def test_sessions_hold_their_lengths_and_seeds_share_them():
    sizes = sessions.session_lengths(DOCQA)
    assert all(1536 <= d <= 2560 and len(t) == 4 for d, t in sizes)
    assert all(32 <= q <= 128 and 64 <= a <= 256 for _, t in sizes for q, a in t)
    a, b = sessions.make(DOCQA, 1, 102400, 60), sessions.make(DOCQA, 2, 102400, 60)
    assert [len(d) for d, _ in a.sessions] == [len(d) for d, _ in b.sessions]
    assert not np.array_equal(a.sessions[0][0], b.sessions[0][0])
