"""The configuration files as the program runs them, the weights made from
the seed, and the plain reference against the program's own forward pass
at a tiny size in float32."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as bm
from bench.arch import moe_decoder as arch
from bench_tiny import make_root
from bench.references import moe_decoder
from repro.models import RunCtx, build_model

REPO = Path(__file__).resolve().parents[2]


def _cfg(tiny_root):
    return json.loads((tiny_root[0] / "bench" / "configs" / "tiny.json").read_text())


@pytest.mark.parametrize("name", ["mixtral-8x7b-pp8", "deepseek-moe-16b-pp4"])
def test_published_configs_map_onto_the_program(name):
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    mc = arch.program_config(cfg)
    assert (mc.d_model, mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.vocab) == \
        (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], 128,
         cfg["vocab_size"])
    assert mc.n_layers == cfg["num_hidden_layers"] and mc.rms_eps == cfg["rms_norm_eps"]
    assert mc.moe.top_k == cfg["num_experts_per_tok"] and mc.rope_theta == cfg["rope_theta"]
    # every key changed from the source is listed, and the source says what it was
    assert set(cfg["reduced"]) <= set(cfg) and cfg["source"].startswith("https://")


def test_a_router_the_program_cannot_run_is_refused():
    cfg = json.loads((REPO / "bench" / "configs" / "deepseek-moe-16b-pp4.json").read_text())
    cfg["norm_topk_prob"] = False
    with pytest.raises(ValueError, match="norm_topk_prob"):
        arch.program_config(cfg)


def test_weights_come_from_the_seed(tiny_root):
    model = build_model(arch.program_config(_cfg(tiny_root)))
    dev = jax.devices()[0]
    a, b = (bm.make_weights(model, 2**31 + 5, jnp.bfloat16, dev) for _ in range(2))
    c = bm.make_weights(model, 6, jnp.bfloat16, dev)
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b), jax.tree.leaves(c)):
        assert x.dtype == jnp.bfloat16 and np.array_equal(x, y)
    assert not np.array_equal(a["lm_head"]["w"], c["lm_head"]["w"])
    assert np.all(np.asarray(a["final_norm"]["w"]) == 1)


@pytest.mark.parametrize("family", ["mixtral", "deepseek"])
def test_reference_matches_the_program_forward_in_float32(tmp_path, family):
    root, _, _ = make_root(tmp_path, family)
    cfg = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    model = build_model(arch.program_config(cfg))
    params = bm.make_weights(model, 11, jnp.float32, jax.devices()[0])
    tokens = np.random.default_rng(0).integers(1, cfg["vocab_size"], 70).astype(np.int32)
    want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]},
                            RunCtx(moe_strategy="dropless", attn_backend="xla"))
    (got,) = moe_decoder.forward(params, cfg, [tokens], [len(tokens)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]), rtol=2e-4, atol=2e-4)
    (fp8,) = moe_decoder.forward(params, cfg, [tokens], [len(tokens)], quant="fp8")
    assert np.max(np.abs(np.asarray(fp8) - np.asarray(got))) > 1e-2
