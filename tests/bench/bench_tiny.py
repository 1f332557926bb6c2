"""A tiny cell for the benchmark's CPU tests: the harness's own files under a
temporary root, with a configuration of the same families at tiny widths."""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# tiny widths; Mixtral keeps its published experts, vocabulary and the cut's
# depth, DeepSeek its vocabulary: the ties near the top of the logits and among
# the experts set how far the float8 control's tokens miss
TINY_MIXTRAL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                    intermediate_size=128, num_local_experts=8, vocab_size=32000,
                    num_hidden_layers=4)
TINY_DEEPSEEK = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                     intermediate_size=160, moe_intermediate_size=32, n_routed_experts=8,
                     num_experts_per_tok=3, vocab_size=102400, num_hidden_layers=3)
TINY_ENGINE = dict(max_slots=4, num_pages=256, max_seq=320, prefill_chunk=32)
TINY_CHAT = dict(kind="chat", loop="closed", clients=4, ramp_s=0.5, pool=256,
                 prompt=dict(median=20, sigma=0.8, min=4, max=100),
                 output=dict(mean=32, max=64))
TINY_SESSIONS = dict(kind="sessions", loop="closed", clients=4, ramp_s=0.5, pool=64,
                     document=[60, 120], turns=3, question=[4, 12], answer=[16, 48])


# a committed cell of each family, whose limits the tiny cell is held to
FAMILY_CELL = {"mixtral": "mixtral-chat-c64", "deepseek": "deepseek-docqa-c12"}


def make_root(tmp: Path, family: str = "mixtral", limits_of: str = ""):
    """(root, bench, cell) of one tiny cell beside the real harness files,
    held to the limits of the committed cell ``limits_of`` (by default the
    family's own)."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    src = {"mixtral": "mixtral-8x7b-pp8", "deepseek": "deepseek-moe-16b-pp4"}[family]
    cfg = json.loads((REPO / "bench" / "configs" / f"{src}.json").read_text())
    cfg.update(TINY_MIXTRAL if family == "mixtral" else TINY_DEEPSEEK, name="tiny")
    cfg["engine"].update(TINY_ENGINE)
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = TINY_CHAT if family == "mixtral" else TINY_SESSIONS
    (tmp / "bench" / "traffic" / "tiny.json").write_text(json.dumps(mix))
    shutil.copy(tmp / "bench" / "limits" / f"{limits_of or FAMILY_CELL[family]}.json",
                tmp / "bench" / "limits" / "tiny-cell.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    cell = {"name": "tiny-cell", "config": "tiny", "traffic": "tiny", "chips": 1, "why": "test"}
    bench["workloads"].append(cell)
    for m in bench["end_to_end"] + bench["per_layer"]:     # the tiny cell reports all
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [cell["name"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp, bench, cell

