"""The harness finds a configuration, a traffic mix and a per-layer metric
added as files alone, with no edit to its code."""
import json
from pathlib import Path

import pytest

from bench import registry

REPO = Path(__file__).resolve().parents[2]


def test_added_files_are_found_by_name(tiny_root):
    root, bench, _ = tiny_root
    (root / "bench" / "traffic" / "burst.py").write_text(
        "def make(p, seed, vocab, horizon_s):\n    return ('burst', p['x'], seed)\n")
    (root / "bench" / "traffic" / "my-mix.json").write_text(json.dumps({"kind": "burst", "x": 3}))
    (root / "bench" / "metrics" / "my_metric.py").write_text(
        "def read(run):\n    return 2 * run\n")
    cfg = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    (root / "bench" / "configs" / "other.json").write_text(json.dumps(dict(cfg, name="other")))
    bench["configs"].append({"name": "other", "source": "s", "file": "bench/configs/other.json",
                             "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "other.my-mix", "config": "other", "traffic": "my-mix",
                               "chips": 1, "why": "w"})
    bench["per_layer"].append({"name": "my_metric", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "engine",
                               "moves": "output_tok_s", "workloads": ["other.my-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = registry.load(root)
    cell = registry.cell(bench, "other.my-mix")
    assert registry.config(root, bench, cell["config"])["name"] == "other"
    mix = registry.traffic(root, cell["traffic"])
    assert registry.generator(root, mix["kind"]).make(mix, 5, 0, 1.0) == ("burst", 3, 5)
    assert registry.metric_reader(root, "my_metric").read(21) == 42
    names = [m["name"] for m in registry.metrics_of(bench, "per_layer", "other.my-mix")]
    assert "my_metric" in names and "prefix_hit_share" not in names
    assert "my_metric" not in [m["name"] for m in registry.metrics_of(bench, "per_layer",
                                                                       "tiny-cell")]


def test_every_listed_file_exists_and_unknown_names_fail(tiny_root):
    root, _, _ = tiny_root
    bench = registry.load(REPO)
    for cell in bench["workloads"]:
        cfg = registry.config(REPO, bench, cell["config"])
        mix = registry.traffic(REPO, cell["traffic"])
        registry.generator(REPO, mix["kind"])
        registry.reference(REPO, cfg["reference"])
        limits = registry.limits(REPO, cell["name"])
        assert limits and all(v > 0 for v in limits.values())
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(REPO, m["name"]).read)
    with pytest.raises(KeyError):
        registry.cell(bench, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        registry.metric_reader(root, "no_such_metric")
