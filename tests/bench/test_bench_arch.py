"""A configuration names its architecture module, and every reader reaches
the module's counts through the run: a new family and a new kernel's
roofline are added with files alone, and the existing readers read what
they read before the counts moved into ``bench/arch/moe_decoder.py``."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, registry
from bench.client import Job, Sent
from bench.model import make_weights
from bench.run import RunRecord
from repro.core.metrics import Request
from repro.core.timeline import StepRecord
from repro.models import build_model

REPO = Path(__file__).resolve().parents[2]
PEAK = {"flops_bf16": 197e12, "hbm_bw": 819e9}

# ------------------------------------------------ a family added as files
TOY_ARCH = '''
"""A dense decoder of plain attention layers (no experts)."""


def widths(cfg):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, H=H, Hkv=cfg["num_key_value_heads"], hd=d // H,
                f=cfg["intermediate_size"], L=cfg["num_hidden_layers"], V=cfg["vocab_size"])


def check_supported(cfg):
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("toy: silu only")


def program_config(cfg):
    from repro.configs.base import LayerGroup, ModelConfig
    check_supported(cfg)
    w = widths(cfg)
    return ModelConfig(name=cfg["name"], family="dense", n_layers=w["L"], d_model=w["d"],
                       n_heads=w["H"], n_kv_heads=w["Hkv"], head_dim=w["hd"], d_ff=w["f"],
                       vocab=w["V"], layer_groups=(LayerGroup("A", w["L"]),))


def layer_kinds(w):
    return ["dense"] * w["L"]


def matmul_params(w, kind):
    d, H, Hkv, hd = w["d"], w["H"], w["Hkv"], w["hd"]
    return 2 * d * H * hd + 2 * d * Hkv * hd + 3 * d * w["f"]


def attn_work(w, rows, nbytes=2):
    H, Hkv, hd = w["H"], w["Hkv"], w["hd"]
    flops = sum(4 * H * hd * (n * s + n * (n + 1) // 2) for s, n in rows)
    nb = sum((s + n) * Hkv * hd * 2 + 2 * n * H * hd for s, n in rows) * nbytes
    return float(flops), float(nb)


def gmm_work(w, tokens, nbytes=2, reached=None):
    return 0.0, 0.0


def model_flops(w, tokens, rows, attn_rows):
    per_token = sum(2 * matmul_params(w, k) for k in layer_kinds(w))
    attn = attn_work(w, attn_rows)[0] * w["L"]
    return float(per_token * tokens + attn + 2 * w["d"] * w["V"] * rows)


FAN_IN = {"wg": lambda shape: 10**8}          # an override by parameter name
'''

TOY_KERNEL_READER = '''
"""Kernel ``toy_attn_pallas``: least time of the traced steps' attention
over its device time, percent."""
from bench.flops import least_time
from bench.metrics._work import traced_steps


def read(run):
    device_s = (run.trace or {}).get("kernel_s", {}).get("toy_attn_pallas", 0.0)
    steps = traced_steps(run)
    if not steps or not device_s:
        return None
    least = sum(run.widths["L"] * least_time(*run.arch.attn_work(run.widths, work["prefill"]),
                                             run.peak)
                for _, work in steps if work["prefill"])
    return 100.0 * least / device_s
'''

TOY_CFG = dict(name="toy", arch="toy_dense", reference="moe_decoder", hidden_size=64,
               num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
               num_hidden_layers=2, vocab_size=512, rope_theta=10000.0, rms_norm_eps=1e-6,
               torch_dtype="bfloat16", source="test",
               engine=dict(max_slots=4, page_size=16, num_pages=64, max_seq=256,
                           prefill_chunk=32, cache_dtype="bfloat16", greedy=True))


def _step(n, prefill_work, decode_ctx, prefill_experts=(), decode_experts=(), deliver_s=0.0):
    tokens = sum(k for _, k in prefill_work)
    return StepRecord(step=n, t0=12.0 + n / 10, t1=12.08 + n / 10, budget=576,
                      tokens_packed=tokens + len(decode_ctx), n_admitted=0,
                      prefill_rows=len(prefill_work), prefill_tokens=tokens,
                      decode_rows=len(decode_ctx), decode_tokens=len(decode_ctx),
                      drafted_tokens=0, accepted_tokens=0, occupancy=len(decode_ctx),
                      max_slots=64, queue_depth=0, kv_free_pages=0, kv_total_pages=3072,
                      preemptions=0, cow_pages=0, prefill_work=tuple(prefill_work),
                      decode_ctx=tuple(decode_ctx), prefill_experts=prefill_experts,
                      decode_experts=decode_experts, deliver_s=deliver_s)


def _run(cfg, arch, steps, kernel_s):
    """A finished, traced run over ``steps`` made by hand."""
    plans = {("r0", s.step): {"prefill": list(s.prefill_work), "decode": list(s.decode_ctx)}
             for s in steps}
    sent, server = [], {}
    for i in range(10):
        s = Sent(f"c{i}", i, Job([1] * (100 + 10 * i), 4, f"q{i}"), t_due=10.0 + i)
        sent.append(s)
        req = Request(f"c{i}", [1] * 100, max_new_tokens=4)
        req.t1, req.t2, req.replica_id = s.t_due, s.t_due + 0.01 * (i + 1), "r0"
        server[s.rid] = req
    trace = {"window_s": 0.31, "busy_s": 0.27, "steps": [("r0", s.step) for s in steps],
             "kernel_s": kernel_s, "device_ops": [], "idle_gaps": []}
    return RunRecord(cell={"name": "c"}, cfg=cfg, arch=arch, widths=arch.widths(cfg), chips=1,
                     t_open=10.0, t_close=20.0, sent=sent, server=server, steps={"r0": steps},
                     stats_open={"r0": {"prefix_cached_tokens": 100.0}},
                     stats_close={"r0": {"prefix_cached_tokens": 350.0}},
                     peak=PEAK, trace=trace, plans=plans)


def test_a_family_and_a_kernel_are_added_with_files_alone(tiny_root):
    root, bench, _ = tiny_root
    (root / "bench" / "arch" / "toy_dense.py").write_text(TOY_ARCH)
    (root / "bench" / "metrics" / "toy_attn_roofline.py").write_text(TOY_KERNEL_READER)
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(TOY_CFG))
    bench["configs"].append({"name": "toy", "source": "test", "file": "bench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-cell", "config": "toy", "traffic": "tiny",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:                    # the toy cell reports them all
        m["workloads"] = m.get("workloads", []) + ["toy-cell"]
    bench["per_layer"].append({"name": "toy_attn_roofline", "unit": "%", "better": "higher",
                               "source": "device_trace", "layer": "kernels",
                               "moves": "output_tok_s", "workloads": ["toy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = registry.load(root)
    cfg = registry.config(root, bench, registry.cell(bench, "toy-cell")["config"])
    arch = registry.arch(root, cfg["arch"])
    model = build_model(arch.program_config(cfg))
    params = make_weights(model, 2**31 + 9, jnp.bfloat16, jax.devices()[0], arch.FAN_IN)
    layer = params["groups"][0]["layers"][0]
    assert layer["attn"]["wq"].shape == (2, 64, 4, 16)
    assert float(jnp.std(layer["mlp"]["wg"].astype(jnp.float32))) < 1e-3      # FAN_IN
    assert 0.05 < float(jnp.std(layer["mlp"]["wi"].astype(jnp.float32))) < 0.2   # 1/sqrt(64)

    steps = [_step(1, [(0, 32), (64, 32)], range(40, 43)), _step(2, [], range(50, 54))]
    run = _run(cfg, arch, steps, {"toy_attn_pallas": 1e-6, "gmm_pallas": 0.0})
    names = [m["name"] for m in registry.metrics_of(bench, "per_layer", "toy-cell")]
    got = {n: registry.metric_reader(root, n).read(run) for n in names}
    w = arch.widths(cfg)
    least = 2 * flops.least_time(*arch.attn_work(w, [(0, 32), (64, 32)]), PEAK)
    assert got["toy_attn_roofline"] == pytest.approx(100 * least / 1e-6)
    want = (arch.model_flops(w, 67, 5, [(0, 32), (64, 32), (40, 1), (41, 1), (42, 1)])
            + arch.model_flops(w, 4, 4, [(50, 1), (51, 1), (52, 1), (53, 1)]))
    assert got["step_mfu"] == pytest.approx(100 * want / 0.31 / 197e12)
    # no experts: the grouped-matmul readers find nothing to read
    assert got["gmm_roofline"] is None and got["gmm_expert_roofline"] is None


# ---------------------------------- the existing readers, read as before
# each reader's value on the run below, recorded before the architecture's
# counts moved out of bench/flops.py and bench/model.py
BEFORE = {
    ("attn_roofline", "mixtral-8x7b-pp8"): 3.75058745147518,
    ("decode_fill", "mixtral-8x7b-pp8"): 56.25,
    ("deliver_ms", "mixtral-8x7b-pp8"): 4.0,
    ("gmm_expert_roofline", "mixtral-8x7b-pp8"): 74.77645597069596,
    ("gmm_roofline", "mixtral-8x7b-pp8"): 26.780085631716776,
    ("idle_share", "mixtral-8x7b-pp8"): 12.903225806451601,
    ("iter_ms", "mixtral-8x7b-pp8"): 103.33333333333333,
    ("prefix_hit_share", "mixtral-8x7b-pp8"): 17.24137931034483,
    ("queue_wait_p90_s", "mixtral-8x7b-pp8"): 0.08999999999999986,
    ("replica_spread", "mixtral-8x7b-pp8"): None,
    ("step_mfu", "mixtral-8x7b-pp8"): 4.380860428072704,
    ("attn_roofline", "deepseek-moe-16b-pp4"): 9.59304205128205,
    ("decode_fill", "deepseek-moe-16b-pp4"): 56.25,
    ("deliver_ms", "deepseek-moe-16b-pp4"): 4.0,
    ("gmm_expert_roofline", "deepseek-moe-16b-pp4"): 37.61281406593406,
    ("gmm_roofline", "deepseek-moe-16b-pp4"): 6.176930818684666,
    ("idle_share", "deepseek-moe-16b-pp4"): 12.903225806451601,
    ("iter_ms", "deepseek-moe-16b-pp4"): 103.33333333333333,
    ("prefix_hit_share", "deepseek-moe-16b-pp4"): 17.24137931034483,
    ("queue_wait_p90_s", "deepseek-moe-16b-pp4"): 0.08999999999999986,
    ("replica_spread", "deepseek-moe-16b-pp4"): None,
    ("step_mfu", "deepseek-moe-16b-pp4"): 1.7749299082626495,
}


def _config_run(name):
    """Three traced steps of the configuration: prefills at two contexts,
    decode rows, and the experts each call reached in each MoE layer."""
    bench = registry.load(REPO)
    cfg = registry.config(REPO, bench, name)
    arch = registry.arch(REPO, cfg["arch"])
    w = arch.widths(cfg)
    n_moe, E = arch.layer_kinds(w).count("moe"), w["E"]
    steps = [_step(1, [(0, 256), (1536, 256)], range(100, 132), (E,) * n_moe,
                   tuple(min(E, 6 + i) for i in range(n_moe)), 0.004),
             _step(2, [], range(200, 264), (), (E - 1,) * n_moe, 0.006),
             _step(3, [(2048, 200)], range(300, 312), (E,) * n_moe, (E // 2,) * n_moe, 0.002)]
    return _run(cfg, arch, steps, {"gmm_pallas": 0.08, "chunked_prefill_pallas": 0.02})


@pytest.mark.parametrize("reader,config", sorted(BEFORE))
def test_each_reader_reads_as_before(reader, config):
    got = registry.metric_reader(REPO, reader).read(_config_run(config))
    want = BEFORE[(reader, config)]
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


def test_each_configuration_names_its_module():
    bench = registry.load(REPO)
    for c in bench["configs"]:
        cfg = registry.config(REPO, bench, c["name"])
        arch = registry.arch(REPO, cfg["arch"])
        for fn in ("check_supported", "program_config", "widths", "layer_kinds",
                   "matmul_params", "attn_work", "gmm_work", "model_flops"):
            assert callable(getattr(arch, fn)), (c["name"], fn)
        assert np.isfinite(arch.model_flops(arch.widths(cfg), 1, 1, [(0, 1)]))
