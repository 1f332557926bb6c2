"""Roofline analysis from compiled dry-run artifacts, against the published
peaks of one chip (``PEAKS``, keyed by JAX's ``device_kind``):

  compute term    = HLO_FLOPs_per_device / peak_flops_bf16
  memory term     = HLO_bytes_per_device / hbm_bw
  collective term = collective_bytes_per_device / ici_bw  (one link)

``cost_analysis()`` supplies FLOPs / bytes of the SPMD-partitioned
per-device program. Collective bytes are NOT in cost_analysis — we parse the
optimized HLO and sum the result-buffer sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute (async -start
forms included). Result sizes are per-device post-partitioning, matching the
per-device roofline denominators.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict

# Per-chip peaks by ``jax.devices()[0].device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16,
# 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (four
# links of 50 GB/s; the collective term assumes one).
PEAKS = {
    "TPU v5 lite": {
        "name": "TPU v5e",
        "peak_flops_bf16": 197e12,
        "hbm_bw": 819e9,
        "ici_bw": 50e9,
        "hbm_capacity": 16e9,
    },
}
V5E_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of this kind; a kind without a table
    entry is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes moved by each collective type (result-buffer sizes)."""
    out: Dict[str, int] = {op: 0 for op in _COLL_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        lhs, _, rhs = line.partition("=")
        rhs = rhs.strip()
        m = re.match(r"^(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+([a-z0-9-]+)", rhs)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        base = op.removesuffix("-start").removesuffix("-done")
        if base in _COLL_OPS and not op.endswith("-done"):
            out[base] += _shape_bytes(type_str)
            out["count"] += 1
    out["total"] = sum(out[o] for o in _COLL_OPS)
    return out


@dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> Dict[str, float]:
        return {"compute_s": self.compute_s, "memory_s": self.memory_s,
                "collective_s": self.collective_s, "dominant": self.dominant}


def roofline(flops_per_dev: float, bytes_per_dev: float,
             coll_bytes_per_dev: float, hw: Dict[str, float]) -> RooflineTerms:
    return RooflineTerms(
        compute_s=flops_per_dev / hw["peak_flops_bf16"],
        memory_s=bytes_per_dev / hw["hbm_bw"],
        collective_s=coll_bytes_per_dev / hw["ici_bw"],
    )


def local_bytes(shape_dtype_tree, spec_tree, mesh) -> int:
    """Per-device bytes of a sharded tree: leaf size / prod(assigned axes)."""
    import jax
    import numpy as np
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 0
    leaves_v, treedef = jax.tree.flatten(shape_dtype_tree)
    leaves_p = treedef.flatten_up_to(spec_tree)
    for v, p in zip(leaves_v, leaves_p):
        n = int(np.prod(v.shape)) if v.shape else 1
        denom = 1
        spec = getattr(p, "spec", p)          # NamedSharding or PartitionSpec
        for entry in tuple(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                denom *= sizes.get(a, 1)
        total += (n // max(denom, 1)) * v.dtype.itemsize
    return total


def model_flops(cfg, shape) -> float:
    """Useful-FLOPs yardstick of a training step: 6·N·D (fwd+bwd), with N =
    active params for MoE and D = the tokens of the global batch."""
    return 6.0 * cfg.active_param_count() * shape.global_batch * shape.seq_len
