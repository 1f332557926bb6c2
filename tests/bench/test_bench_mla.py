"""The latent-attention configuration as the harness runs it: found by name,
its weights made with the latent fan-ins, its attention counted as a lower
bound of what either form computes, its kernel's roofline read from a
trace, the program held to the plain reference, and the float8 control
refused under the cell's limits."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import registry
from bench.model import make_weights
from bench.run import RunRecord, run_cell
from bench_tiny import TINY_ENGINE, make_root
from repro.core.timeline import StepRecord
from repro.models import RunCtx, build_model

REPO = Path(__file__).resolve().parents[2]
CELL = "dsv2lite-longdoc-c12"
PEAK = {"flops_bf16": 197e12, "hbm_bw": 819e9}
# the published configuration at tiny widths: its experts per token, shared
# experts, dense first layer, unnormalized routing and vocabulary kept; YaRN
# over an original context of 64, so that its ramp bites in a tiny session
TINY_MLA = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                intermediate_size=160, moe_intermediate_size=32, n_routed_experts=8,
                num_experts_per_tok=3, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=16, v_head_dim=16, num_hidden_layers=3)


def _tiny_cfg(**over):
    cfg = json.loads((REPO / "bench" / "configs" / "deepseek-v2-lite-pp4.json").read_text())
    cfg.update(TINY_MLA, name="tiny", **over)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], original_max_position_embeddings=64)
    cfg["engine"].update(TINY_ENGINE)
    return cfg


def _arch():
    return registry.arch(REPO, "mla_moe_decoder")


# ------------------------------------------------ configuration and weights
def test_the_configuration_is_the_published_one_cut_in_depth():
    bench = registry.load(REPO)
    cell = registry.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc-c12"
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"]
    cfg = registry.config(REPO, bench, cell["config"])
    assert cfg["norm_topk_prob"] is False and cfg["num_hidden_layers"] == 7
    model_cfg = _arch().program_config(cfg)
    assert model_cfg.moe.norm_topk is False and model_cfg.mla.cache_dim == 576
    assert model_cfg.rope_yarn.factor == 40 and model_cfg.n_layers == 7
    params = jax.eval_shape(lambda: build_model(model_cfg).init_params(jax.random.PRNGKey(0),
                                                                        jnp.bfloat16))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert 4.00e9 < n < 4.02e9                       # ≈ 4.01 B parameters, 8.02 GB
    for bad in (dict(q_lora_rank=1536), dict(scoring_func="sigmoid"),
                dict(rope_scaling={"type": "linear", "factor": 2})):
        with pytest.raises(ValueError):
            _arch().check_supported(dict(cfg, **bad))


def test_weights_use_the_latent_fan_in():
    cfg = _tiny_cfg(kv_lora_rank=512)
    arch = _arch()
    model = build_model(arch.program_config(cfg))
    w = make_weights(model, 2**31 + 7, jnp.float32, jax.devices()[0], arch.FAN_IN)
    attn = w["groups"][1]["layers"][0]["attn"]
    assert (np.asarray(attn["kv_norm"]["w"]) == 1).all()
    assert np.std(np.asarray(attn["wk_b"])) == pytest.approx(512 ** -0.5, rel=0.03)
    assert np.std(np.asarray(attn["wv_b"])) == pytest.approx(512 ** -0.5, rel=0.03)
    assert np.std(np.asarray(attn["wkv_a"])) == pytest.approx(64 ** -0.5, rel=0.03)


# ------------------------------------------------ counts
def _w():
    cfg = json.loads((REPO / "bench" / "configs" / "deepseek-v2-lite-pp4.json").read_text())
    return _arch().widths(cfg)


@pytest.mark.parametrize("s,n", [(0, 256), (17000, 256), (17500, 1), (3, 1)])
def test_attn_work_is_the_lesser_form_and_a_lower_bound(s, n):
    w = _w()
    flops, nbytes = _arch().attn_work(w, [(s, n)])
    pairs = n * s + n * (n + 1) // 2
    absorbed = 2 * 16 * (576 + 512) * pairs
    expanded = 2 * 16 * (192 + 128) * pairs + 2 * 512 * 16 * 256 * (s + n)
    assert flops == min(absorbed, expanded)
    assert nbytes == 2 * ((s + n) * 576 + n * 16 * 192 + n * 16 * 128)
    # at most what the absorbed kernel does: every query scores every
    # resident row over 640 columns and sums 512, reads each row in its 640
    # lanes once, its 640-wide queries in and 512-wide outputs out
    assert flops <= 2 * 16 * (640 + 512) * n * (s + n)
    assert nbytes <= 2 * ((s + n) * 640 + n * 16 * (640 + 512))
    # and at most what the expanded form does in XLA
    assert flops <= expanded


def test_decode_is_absorbed_and_a_long_prefill_chunk_expanded():
    w = _w()
    f_dec, _ = _arch().attn_work(w, [(17500, 1)])
    assert f_dec == 2 * 16 * 1088 * 17501                     # the absorbed form
    f_pre, _ = _arch().attn_work(w, [(17000, 256)])
    pairs = 256 * 17000 + 256 * 257 // 2
    assert f_pre == 2 * 16 * 320 * pairs + 2 * 512 * 16 * 256 * 17256


# ------------------------------------------------ the kernel's roofline
def _step(n, prefill_work, decode_ctx):
    tokens = sum(k for _, k in prefill_work)
    return StepRecord(step=n, t0=12.0 + n / 10, t1=12.08 + n / 10, budget=524,
                      tokens_packed=tokens + len(decode_ctx), n_admitted=0,
                      prefill_rows=len(prefill_work), prefill_tokens=tokens,
                      decode_rows=len(decode_ctx), decode_tokens=len(decode_ctx),
                      drafted_tokens=0, accepted_tokens=0, occupancy=len(decode_ctx),
                      max_slots=12, queue_depth=0, kv_free_pages=0, kv_total_pages=20480,
                      preemptions=0, cow_pages=0, prefill_work=tuple(prefill_work),
                      decode_ctx=tuple(decode_ctx))


def _run(kernel_s):
    cfg = json.loads((REPO / "bench" / "configs" / "deepseek-v2-lite-pp4.json").read_text())
    arch = _arch()
    steps = [_step(1, [(16000, 256), (12000, 256)], [17000] * 10),
             _step(2, [], [17000 + i for i in range(12)])]
    plans = {("r0", s.step): {"prefill": list(s.prefill_work), "decode": list(s.decode_ctx)}
             for s in steps}
    trace = {"window_s": 0.2, "busy_s": 0.19, "steps": [("r0", s.step) for s in steps],
             "kernel_s": kernel_s, "device_ops": [], "idle_gaps": []}
    return RunRecord(cell={"name": CELL}, cfg=cfg, arch=arch, widths=arch.widths(cfg), chips=1,
                     t_open=10.0, t_close=20.0, sent=[], server={}, steps={"r0": steps},
                     stats_open={}, stats_close={}, peak=PEAK, trace=trace, plans=plans)


def test_mla_roofline_reads_a_synthetic_run():
    reader = registry.metric_reader(REPO, "mla_roofline")
    run = _run({"mla_paged_pallas": 0.05, "gmm_pallas": 0.01})
    arch, w = run.arch, run.widths
    least = 7 * sum(max(f / PEAK["flops_bf16"], b / PEAK["hbm_bw"]) for f, b in (
        arch.attn_work(w, [(16000, 256), (12000, 256)]), arch.attn_work(w, [(17000, 1)] * 10),
        arch.attn_work(w, [(17000 + i, 1) for i in range(12)])))
    assert reader.read(run) == pytest.approx(100 * least / 0.05)
    assert 0 < reader.read(run) <= 100
    # a program without the kernel (the parent of this configuration) reads nothing
    assert reader.read(_run({"chunked_prefill_pallas": 0.05})) is None


# ------------------------------------------------ program against reference
def test_the_program_serves_what_the_reference_computes():
    """f32 weights: the program's prefill chunks and decode through the
    latent pool, and its full forward pass, give the plain reference's
    logits. Both compute in f32 and order their sums differently."""
    cfg = _tiny_cfg()
    arch = _arch()
    model = build_model(arch.program_config(cfg))
    params = make_weights(model, 2**31 + 11, jnp.float32, jax.devices()[0], arch.FAN_IN)
    ref = registry.reference(REPO, "mla_decoder")
    toks = np.random.default_rng(5).integers(1, 102400, 90).astype(np.int32)
    want = ref.forward(params, cfg, [toks], [90])[0]
    ctx = RunCtx(attn_backend="xla", moe_strategy="dropless")
    full, _ = model.forward(params, {"tokens": jnp.asarray(toks)[None]}, ctx)
    np.testing.assert_allclose(np.asarray(full)[0], want, atol=1e-4)

    ps, C = 16, 32
    cache = model.init_cache(1, 96, jnp.float32, kind="paged", page_size=ps, num_pages=8)
    pt = jnp.asarray([[7, 3, 5, 1, 2, 6]], jnp.int32)
    step = jax.jit(model.decode_chunk, static_argnames=("ctx", "all_logits"))
    got, t = [], 0
    while t < 90:
        n = min(C if t < 64 else 1, 90 - t)
        tok = np.zeros((1, C), np.int32)
        tok[0, :n] = toks[t:t + n]
        lg, cache = step(params, jnp.asarray(tok), cache, jnp.asarray([t], jnp.int32),
                         jnp.asarray([n], jnp.int32), jnp.zeros(1, jnp.int32),
                         jnp.asarray([t == 0]), ctx=ctx, page_table=pt, all_logits=True)
        got.append(np.asarray(lg)[0, :n])
        t += n
    np.testing.assert_allclose(np.concatenate(got), want, atol=1e-4)


# ------------------------------------------------ the control
def test_control_fails_where_the_served_path_passes(tmp_path):
    root, bench, cell = make_root(tmp_path, "deepseek", limits_of=CELL)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(_tiny_cfg()))
    limits = json.loads((root / "bench" / "limits" / f"{CELL}.json").read_text())
    res = run_cell(root, bench, cell, seed=2**31 + 303, seconds=120.0, trace=False,
                   devices=jax.devices(), control=True, window_tokens=280)
    assert all(res["served"][k] <= v for k, v in limits.items()), res["served"]
    assert not res["correct"]
    assert any(res["check"][k]["value"] > v for k, v in limits.items()), res["check"]
