"""Roofline machinery: HLO collective parsing, term math, local-bytes
sharding arithmetic, workload generator stats."""
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.data.workload import WorkloadSpec, sample_workload
from repro.launch.roofline import (V5E_KIND, model_flops, parse_collective_bytes,
                                   peaks, roofline)

HLO = """
ENTRY %main {
  %p0 = bf16[16,512,2048]{2,1,0} parameter(0)
  %ag = bf16[16,512,2048]{2,1,0} all-gather(%p0), dimensions={1}
  %ar.1 = f32[1024,688]{1,0} all-reduce(%x), to_apply=%sum
  %a2a = bf16[16,8,6144]{2,1,0} all-to-all(%buf), dimensions={0}
  %rs-start = f32[64]{0} reduce-scatter-start(%g)
  %agd = bf16[4,4]{1,0} all-gather-done(%h)
  %cp = u32[8,128]{1,0} collective-permute(%q)
  %dot = f32[8,8]{1,0} dot(%a, %b)
}
"""


def test_parse_collective_bytes():
    out = parse_collective_bytes(HLO)
    assert out["all-gather"] == 16 * 512 * 2048 * 2          # -done not counted twice
    assert out["all-reduce"] == 1024 * 688 * 4
    assert out["all-to-all"] == 16 * 8 * 6144 * 2
    assert out["reduce-scatter"] == 64 * 4                   # -start counted
    assert out["collective-permute"] == 8 * 128 * 4
    assert out["total"] == sum(out[k] for k in
                               ("all-gather", "all-reduce", "all-to-all",
                                "reduce-scatter", "collective-permute"))


def test_roofline_terms_and_dominant():
    v5e = peaks(V5E_KIND)
    t = roofline(197e12, 819e9, 25e9, v5e)     # 1s compute, 1s memory, 0.5s coll
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 1.0) < 1e-9
    assert abs(t.collective_s - 0.5) < 1e-9
    t2 = roofline(1e12, 1e9, 100e9, v5e)
    assert t2.dominant == "collective"
    assert t2.bound_s == t2.collective_s


def test_peaks_refuse_unknown_device_kind():
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")


def test_model_flops_conventions():
    from repro.configs import SHAPES, get_config
    cfg = get_config("mixtral-8x7b")
    n_active = cfg.active_param_count()
    assert model_flops(cfg, SHAPES["train_4k"]) == pytest.approx(
        6.0 * n_active * 256 * 4096)


def test_local_bytes_respects_sharding():
    import jax
    from repro.launch.roofline import local_bytes

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((4, 2))

    tree = {"w": jax.ShapeDtypeStruct((8, 16), np.dtype("float32"))}
    full = local_bytes(tree, {"w": P(None, None)}, FakeMesh())
    half = local_bytes(tree, {"w": P(None, "model")}, FakeMesh())
    eighth = local_bytes(tree, {"w": P("data", "model")}, FakeMesh())
    assert full == 8 * 16 * 4
    assert half == full // 2
    assert eighth == full // 8


def test_workload_stats():
    prompts, outs = sample_workload(WorkloadSpec(n_requests=200, vocab=1000, seed=1))
    lens = np.array([len(p) for p in prompts])
    assert 50 < np.median(lens) < 450         # OpenOrca-ish median around 150
    assert lens.max() <= 2048 and lens.min() >= 2
    assert all(2 <= o <= 512 for o in outs)
    # deterministic per seed
    p2, _ = sample_workload(WorkloadSpec(n_requests=200, vocab=1000, seed=1))
    assert all(np.array_equal(a, b) for a, b in zip(prompts, p2))
