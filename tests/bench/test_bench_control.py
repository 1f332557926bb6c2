"""The control at a size a test run can hold: the plain reference with its
weights in float8, judged by the harness in the served tokens' place under
each committed cell's own limits file, has to come out not correct where the
served path, on the same sample, passes."""
import json

import jax
import pytest

from bench.run import run_cell
from bench_tiny import make_root

# each committed cell, with the family of its configuration
CELLS = {"mixtral-chat-c64": "mixtral", "deepseek-docqa-c12": "deepseek"}
WINDOW_TOKENS = 280


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_control_fails_where_the_served_path_passes(tmp_path, cell_name):
    root, bench, cell = make_root(tmp_path, CELLS[cell_name], limits_of=cell_name)
    limits = json.loads((root / "bench" / "limits" / f"{cell_name}.json").read_text())
    # the window holds as many served tokens as a 2-s one on an idle machine,
    # however long a loaded one takes to serve them
    res = run_cell(root, bench, cell, seed=2**31 + 202, seconds=120.0, trace=False,
                   devices=jax.devices(), control=True, window_tokens=WINDOW_TOKENS)
    assert all(res["served"][k] <= v for k, v in limits.items()), res["served"]
    assert not res["correct"]
    assert any(res["check"][k]["value"] > v for k, v in limits.items()), res["check"]
    assert list(res)[-1] == "check"


def test_sample_holds_each_replicas_longest_then_the_longest():
    import numpy as np

    from bench import check
    from bench.client import Job, Sent

    done = []
    for i in range(40):
        s = Sent(f"r{i}", 0, Job(np.zeros(10 + i, np.int32), 64, f"q{i}"), t_due=0.0)
        s.tokens, s.finished = [1] * 64, True
        done.append(s)
    replica_of = {s.rid: ("a" if i % 4 else "b") for i, s in enumerate(done)}
    sample = check.pick(done, replica_of, seed=7)
    rids = [s.rid for s in sample]
    assert rids[:2] == ["r39", "r36"]                  # the longest of replica a, then of b
    assert rids[2:6] == ["r38", "r37", "r35", "r34"]   # the longest, to half of the tokens
    assert len(sample) == check.SAMPLE_REQUESTS and len(set(rids)) == len(rids)
    assert rids == [s.rid for s in check.pick(done, replica_of, seed=7)]
