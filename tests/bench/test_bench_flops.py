"""Operations and bytes against hand counts, for both configurations, from
their architecture module."""
import json
from pathlib import Path

import pytest

from bench import flops
from bench.arch import moe_decoder as arch

REPO = Path(__file__).resolve().parents[2]


def _w(name):
    return arch.widths(json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text()))


MIX, DS = _w("mixtral-8x7b-pp8"), _w("deepseek-moe-16b-pp4")


def test_widths_as_published():
    assert (MIX["d"], MIX["H"], MIX["Hkv"], MIX["hd"], MIX["E"], MIX["k"], MIX["f"], MIX["V"]) == \
        (4096, 32, 8, 128, 8, 2, 14336, 32000)
    assert (DS["d"], DS["H"], DS["Hkv"], DS["E"], DS["k"], DS["f"], DS["shared"],
            DS["f_dense"], DS["V"], DS["dense_layers"], DS["L"]) == \
        (2048, 16, 16, 64, 6, 1408, 2, 10944, 102400, 1, 7)


def test_params_per_token_by_hand():
    attn_mix = 4096 * 32 * 128 * 2 + 4096 * 8 * 128 * 2            # q, o; k, v
    assert arch.matmul_params(MIX, "moe") == attn_mix + 4096 * 8 + 2 * 3 * 4096 * 14336
    attn_ds = 4 * 2048 * 2048
    assert arch.matmul_params(DS, "dense") == attn_ds + 3 * 2048 * 10944
    assert arch.matmul_params(DS, "moe") == attn_ds + 2048 * 64 + (6 + 2) * 3 * 2048 * 1408
    assert arch.layer_kinds(DS) == ["dense"] + ["moe"] * 6


def test_gmm_work_by_hand():
    f, b = arch.gmm_work(MIX, 64)                    # 64 tokens, top-2: 128 rows
    assert f == 3 * 2 * 128 * 4096 * 14336
    assert b == 2 * (3 * 2 * 4096 * 14336 + 3 * 128 * (4096 + 14336))
    f, b = arch.gmm_work(DS, 12)                     # 72 rows reach >= 6 experts
    assert f == 3 * 2 * 72 * 2048 * 1408
    assert b == 2 * (3 * 6 * 2048 * 1408 + 3 * 72 * (2048 + 1408))
    assert arch.gmm_work(DS, 0) == (0.0, 0.0)


def test_attention_work_by_hand():
    f, b = arch.attn_work(MIX, [(100, 1)])           # one decode row at context 100
    assert f == 4 * 32 * 128 * 101
    assert b == 2 * (101 * 8 * 128 * 2 + 32 * 128 * 2)
    f, b = arch.attn_work(DS, [(0, 3), (10, 2)])     # chunks: causal 1+2+3, 11+12
    assert f == 4 * 16 * 128 * (6 + 23)
    assert b == 2 * ((3 + 12) * 16 * 128 * 2 + 5 * 16 * 128 * 2)


def test_model_flops_and_least_time():
    per_token = 2 * (arch.matmul_params(DS, "dense") + 6 * arch.matmul_params(DS, "moe"))
    got = arch.model_flops(DS, 10, 2, [(0, 10)])
    assert got == per_token * 10 + 7 * 4 * 16 * 128 * 55 + 2 * 2 * 2048 * 102400
    peak = {"flops_bf16": 100.0, "hbm_bw": 10.0}
    assert flops.least_time(200.0, 10.0, peak) == pytest.approx(2.0)
    assert flops.least_time(100.0, 50.0, peak) == pytest.approx(5.0)
