"""The served path's Pallas kernels at Mixtral-8x7B widths, in bf16, compiled
for a described TPU v5e chip (none is attached): the chip's compiler refuses
here what interpret mode lets through, such as an unaligned block or more
VMEM than a kernel may use. Each compile takes a second or two.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and it keeps it until it exits.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.backend import compiled_kernels
from repro.kernels.moe_gmm.kernel import gmm_pallas
from repro.kernels.paged_attention.kernel import chunked_prefill_pallas, mla_paged_pallas

BF16, I32 = jnp.bfloat16, jnp.int32
# Mixtral-8x7B: 32 query / 8 kv heads of 128, 8 experts, d_model 4096,
# d_ff 14336, 4 layers a stage (bench/configs/mixtral-8x7b-pp8.json); the
# engine's pool in 16-token pages (chip_smoke.ENGINE)
H, HKV, D, E, DM, DFF, LAYERS = 32, 8, 128, 8, 4096, 14336, 4
PAGES, PAGE, MAXP, SLOTS, CHUNK_ROWS, CHUNK = 1024, 16, 68, 8, 2, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


# the benchmark cells' sweeps: Mixtral's 64-slot decode over max_seq 2560,
# DeepSeek-MoE-16B's (16 MHA heads of 128) 12-slot decode and 2-row prefill
# pack over max_seq 4096; the prefill pack takes the most VMEM of them
@pytest.mark.parametrize("rows,chunk,h,hkv,maxp", [
    (SLOTS, 1, H, HKV, MAXP), (CHUNK_ROWS, CHUNK, H, HKV, MAXP),
    (64, 1, H, HKV, 160), (12, 1, 16, 16, 256), (CHUNK_ROWS, CHUNK, 16, 16, 256),
], ids=["decode_sweep", "prefill_pack", "mixtral_c64_decode", "deepseek_c12_decode",
        "deepseek_prefill_pack"])
def test_chunked_prefill_compiles_for_v5e(one_chip, rows, chunk, h, hkv, maxp):
    compiled = _compile(
        lambda q, k, v, pt, ln, st: chunked_prefill_pallas(q, k, v, pt, ln, st,
                                                           scale=D ** -0.5),
        one_chip, ((rows, chunk, h, D), BF16), ((PAGES, hkv, PAGE, D), BF16),
        ((PAGES, hkv, PAGE, D), BF16), ((rows, maxp), I32), ((rows,), I32),
        ((rows,), I32))
    assert compiled_kernels(compiled.as_text()) == ["chunked_prefill_pallas"]


GMM_ROWS = (CHUNK_ROWS * CHUNK * 2 // 128 + E) * 128     # top-2 rows, tile-padded


# the experts of every layer of a stage, stacked, read at one layer's index
@pytest.mark.parametrize("k,n", [(DM, DFF), (DFF, DM)], ids=["gate_up", "down"])
def test_gmm_compiles_for_v5e(one_chip, k, n):
    compiled = _compile(lambda x, w, te, layer: gmm_pallas(x, w, te, layer), one_chip,
                        ((GMM_ROWS, k), BF16), ((LAYERS, E, k, n), BF16),
                        ((GMM_ROWS // 128,), I32), ((1,), I32))
    assert compiled_kernels(compiled.as_text()) == ["gmm_pallas"]


# one layer's experts (bf16[8,4096,14336] or bf16[8,14336,4096], with a
# leading 1 or not) as the result of an instruction that a slice or a copy
# names or is: `%constant_dynamic-slice_fusion.6 = bf16[1,8,4096,14336]{...}
# fusion(...)`, `%dynamic_slice.11 = bf16[...] dynamic-slice(...)`, or a
# `copy-start` whose tuple holds one
_EXPERTS = r"bf16\[(1,)?8,(4096,14336|14336,4096)\]"
EXPERT_COPY = re.compile(rf"%[\w.-]*(slice|copy)[\w.-]* = \(?{_EXPERTS}"
                         rf"|= {_EXPERTS}\S* [\w-]*(slice|copy)")


def test_gmm_layer_scan_reads_stacks_in_place(one_chip):
    """A two-layer scan of the three expert matmuls, the stacks closed over
    and the layer's index handed to the kernel: no layer's experts (0.94 GB
    each) are sliced or copied out of a stack, and the program's temporaries
    stay far below one of them."""
    def two_layers(x, wg, wu, wd, te):
        def body(h, layer):
            layer = layer[None]
            g = gmm_pallas(h, wg, te, layer).astype(jnp.float32)
            u = gmm_pallas(h, wu, te, layer).astype(jnp.float32)
            return gmm_pallas((jax.nn.silu(g) * u).astype(BF16), wd, te, layer), None
        return jax.lax.scan(body, x, jnp.arange(2, dtype=I32))[0]

    compiled = _compile(two_layers, one_chip, ((GMM_ROWS, DM), BF16),
                        ((2, E, DM, DFF), BF16), ((2, E, DM, DFF), BF16),
                        ((2, E, DFF, DM), BF16), ((GMM_ROWS // 128,), I32))
    hlo = compiled.as_text()
    assert compiled_kernels(hlo) == ["gmm_pallas"] * 3
    assert not [line for line in hlo.splitlines() if EXPERT_COPY.search(line)]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


# DeepSeek-V2-Lite's latent sweeps (bench/configs/deepseek-v2-lite-pp4.json):
# 16 heads over one pool of 576 values a token in 640-lane rows, values the
# first 512; the 12-slot decode and the 2-row prefill pack over max_seq 24576
@pytest.mark.parametrize("rows,chunk", [(12, 1), (CHUNK_ROWS, CHUNK)],
                         ids=["dsv2lite_decode", "dsv2lite_prefill_pack"])
def test_mla_paged_compiles_for_v5e(one_chip, rows, chunk):
    maxp = 24576 // PAGE
    compiled = _compile(
        lambda q, pool, pt, ln, st: mla_paged_pallas(q, pool, pt, ln, st, scale=0.114721,
                                                     dv=512),
        one_chip, ((rows, chunk, 16, 640), BF16), ((PAGES, PAGE, 640), BF16),
        ((rows, maxp), I32), ((rows,), I32), ((rows,), I32))
    assert compiled_kernels(compiled.as_text()) == ["mla_paged_pallas"]
