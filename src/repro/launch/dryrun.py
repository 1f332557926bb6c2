import os

# The dry-run compiles for 512 virtual CPU devices and must never take an
# accelerator, nor let its --all children (one per cell) compete for one.
# Both settings MUST precede any jax import: jax fixes its platform and
# device count on first init. The caller's other XLA flags are kept.
_DEVICE_FLAG = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
if _DEVICE_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_DEVICE_FLAG}".strip()

__doc__ = """Multi-pod dry-run on the CPU: prove the distribution config is coherent
for meshes of TPU v5e chips (roofline peaks: ``roofline.peaks(V5E_KIND)``).

For every (architecture x training shape) cell, on the single-pod (16,16) and
multi-pod (2,16,16) production meshes, the training step:

    with mesh:
        lowered  = jax.jit(step, in_shardings=...).lower(**input_specs)
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves it fits
        compiled.cost_analysis()     # FLOPs / bytes for the roofline

Results are dumped as JSON under experiments/dryrun/ for the roofline table.
Run a single cell:    python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
Run everything:       python -m repro.launch.dryrun --all   (subprocess per cell)
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import (make_rules, named, param_partition_specs,
                                        partition_spec, use_sharding)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (V5E_KIND, local_bytes, model_flops,
                                   parse_collective_bytes, peaks, roofline)
from repro.models import RunCtx, build_model
from repro.models.params import abstract_params, param_specs
from repro.training.optimizer import AdamWState, adamw_init
from repro.training.train_step import TrainConfig, make_train_step


def _struct(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.vision is not None:
        S_text = S - cfg.vision.n_patches
        return {"tokens": _struct((B, S_text), jnp.int32),
                "labels": _struct((B, S_text), jnp.int32),
                "patches": _struct((B, cfg.vision.n_patches, cfg.vision.d_patch), jnp.bfloat16)}
    if cfg.encoder is not None:
        return {"tokens": _struct((B, S), jnp.int32),
                "labels": _struct((B, S), jnp.int32),
                "frames": _struct((B, S, cfg.d_model), jnp.bfloat16)}
    return {"tokens": _struct((B, S), jnp.int32),
            "labels": _struct((B, S), jnp.int32)}


def _batch_shardings(batch_structs, mesh, rules):
    logical = {"tokens": ("batch", "seq"), "labels": ("batch", "seq"),
               "patches": ("batch", None, None), "frames": ("batch", "seq", None)}
    return {k: named(mesh, partition_spec(v.shape, logical[k], mesh, rules))
            for k, v in batch_structs.items()}


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *, moe_mode: str = "tp",
               seq_shard: bool = False, cost_mode: bool = False,
               block_div: int = 4, quant: str = "none",
               xent_chunk: int = 0, microbatches: int = 1):
    """Build (lowered, t_lower, sizes) for one cell's training step.
    ``cost_mode`` unrolls layers and attention tiles so XLA cost_analysis
    counts every iteration (see the affine calibration in run_cell)."""
    multi_pod = "pod" in mesh.axis_names
    factored = "tensor" in mesh.axis_names           # Exp4 mesh (data,expert,tensor)
    tensor_axis = "tensor" if factored else "model"
    expert_axis = "expert" if factored else None
    rules = make_rules("train", moe=moe_mode, multi_pod=multi_pod,
                       seq_shard=seq_shard, tensor_axis=tensor_axis,
                       expert_axis=expert_axis)
    strategy = ({"tp": "tp_shardmap", "ep": "ep_shardmap"}[moe_mode]
                if cfg.moe is not None else "capacity")
    model = build_model(cfg)
    specs = param_specs(cfg)
    blk = max(shape.seq_len // block_div, 1024) if cost_mode else 1024
    ctx = RunCtx(mesh=mesh, attn_backend="xla", moe_strategy=strategy, remat=True,
                 scan_layers=not cost_mode, attn_unroll=cost_mode,
                 block_q=blk, block_kv=blk,
                 ep_axis=expert_axis or "data", tp_axis=tensor_axis, quant=quant)
    _, step_fn = make_train_step(
        model, TrainConfig(remat=True, xent_chunk=xent_chunk,
                           microbatches=microbatches), ctx)
    params_t = abstract_params(cfg, jnp.float32)
    opt_t = jax.eval_shape(adamw_init, params_t)
    state_t = (opt_t, None)
    batch_t = input_specs(cfg, shape)

    p_sh = jax.tree.map(lambda s: named(mesh, s),
                        param_partition_specs(specs, mesh, rules))
    opt_sh = AdamWState(step=named(mesh, partition_spec((), (), mesh, rules)),
                        m=p_sh, v=p_sh)
    in_sh = (p_sh, (opt_sh, None), _batch_shardings(batch_t, mesh, rules))

    with mesh:
        with use_sharding(mesh, rules):
            t0 = time.time()
            lowered = jax.jit(step_fn, in_shardings=in_sh,
                              donate_argnums=(0, 1)   # params + opt state in place
                              ).lower(params_t, state_t, batch_t)
            t_lower = time.time() - t0
    return lowered, t_lower, {"params_local_bytes": local_bytes(params_t, p_sh, mesh)}


def _compile_costs(lowered) -> Dict[str, float]:
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = parse_collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            **{f"coll_{k}": float(v) for k, v in coll.items()}}


def _variant(cfg: ModelConfig, repeats: List[int], enc_layers: Optional[int]) -> ModelConfig:
    groups = tuple(dataclasses.replace(g, repeats=r)
                   for g, r in zip(cfg.layer_groups, repeats))
    n_layers = sum(g.n_layers for g in groups)
    enc = (dataclasses.replace(cfg.encoder, n_layers=enc_layers)
           if cfg.encoder is not None else None)
    return cfg.scaled(n_layers=n_layers, layer_groups=groups, encoder=enc)


def calibrated_costs(cfg: ModelConfig, shape: ShapeConfig, mesh, *, moe_mode: str,
                     seq_shard: bool, block_div: int = 4,
                     quant: str = "none", xent_chunk: int = 0,
                     microbatches: int = 1) -> Dict[str, Any]:
    """XLA cost_analysis counts loop bodies once, so we measure UNROLLED
    probe variants at small repeat counts and solve the affine model
    cost = base + sum_i repeats_i * c_i  (exact: cost is affine in repeats).
    """
    G = len(cfg.layer_groups)
    has_enc = cfg.encoder is not None
    base_rep = [1] * G
    base_enc = 1 if has_enc else None
    probes = [(base_rep, base_enc)]
    for i in range(G):
        rep = list(base_rep)
        rep[i] = 2
        probes.append((rep, base_enc))
    if has_enc:
        probes.append((base_rep, 2))

    costs = []
    for rep, enc in probes:
        vcfg = _variant(cfg, rep, enc)
        lowered, _, _ = build_cell(vcfg, shape, mesh, moe_mode=moe_mode,
                                   seq_shard=seq_shard, cost_mode=True,
                                   block_div=block_div, quant=quant,
                                   xent_chunk=xent_chunk, microbatches=microbatches)
        costs.append(_compile_costs(lowered))

    keys = costs[0].keys()
    coeffs = [{k: costs[1 + i][k] - costs[0][k] for k in keys} for i in range(G)]
    enc_coeff = ({k: costs[1 + G][k] - costs[0][k] for k in keys} if has_enc else None)
    base = {k: costs[0][k] - sum(c[k] for c in coeffs)
            - (enc_coeff[k] if enc_coeff else 0.0) for k in keys}
    full = {}
    for k in keys:
        v = base[k] + sum(cfg.layer_groups[i].repeats * coeffs[i][k] for i in range(G))
        if enc_coeff:
            v += cfg.encoder.n_layers * enc_coeff[k]
        full[k] = v
    return {"full": full, "base": base,
            "per_group": coeffs, "encoder_coeff": enc_coeff,
            "n_probes": len(probes)}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, moe_mode: str = "tp",
             seq_shard: bool = False, skip_cost: bool = False,
             quant: str = "none", exp4: Optional[str] = None,
             xent_chunk: int = 0, microbatches: int = 1) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    meta: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                            "multi_pod": multi_pod,
                            "moe": moe_mode if cfg.moe is not None else None,
                            "seq_shard": seq_shard, "quant": quant,
                            "exp4": exp4, "xent_chunk": xent_chunk,
                            "microbatches": microbatches}
    if exp4:
        from repro.launch.mesh import make_moe_mesh
        ep, tp = (int(x) for x in exp4.split("x"))
        mesh = make_moe_mesh(ep, tp, chips=256)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(mesh.devices.size)
    meta["mesh"] = list(mesh.devices.shape)

    # ---- 1) production (scanned) lowering: the compile + memory proof ----
    lowered, t_lower, sizes = build_cell(cfg, shape, mesh, moe_mode=moe_mode,
                                         seq_shard=seq_shard, cost_mode=False,
                                         quant=quant, xent_chunk=xent_chunk,
                                         microbatches=microbatches)
    meta["lower_s"] = t_lower
    meta["sizes"] = sizes
    t0 = time.time()
    compiled = lowered.compile()
    meta["compile_s"] = time.time() - t0
    mem = compiled.memory_analysis()
    meta["memory"] = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
    }
    meta["compiled_ok"] = True

    if skip_cost:
        return meta

    # ---- 2) affine-calibrated costs (unrolled probes) ----
    cal = calibrated_costs(cfg, shape, mesh, moe_mode=moe_mode, seq_shard=seq_shard,
                           quant=quant, xent_chunk=xent_chunk,
                           microbatches=microbatches)
    full = cal["full"]
    if microbatches > 1:
        # the gradient-accumulation lax.scan body is counted once by XLA's
        # cost analysis (same pathology the layer calibration fixes) — scale
        # by the trip count
        full = {k: v * microbatches for k, v in full.items()}
    meta["cost"] = {"flops": full["flops"], "bytes_accessed": full["bytes"],
                    "n_probes": cal["n_probes"]}
    meta["collectives"] = {k.removeprefix("coll_"): v for k, v in full.items()
                           if k.startswith("coll_")}

    # ---- 3) roofline, against the chip the production meshes are made of ----
    hw = peaks(V5E_KIND)
    terms = roofline(full["flops"], full["bytes"], full["coll_total"], hw)
    meta["roofline"] = terms.as_dict()
    # memory floor: weights read once — the fused-TPU lower bound; XLA's
    # bytes_accessed counts unfused copies and is the upper bound. Real HBM
    # time lies between.
    meta["roofline"]["memory_floor_s"] = sizes["params_local_bytes"] / hw["hbm_bw"]
    mf = model_flops(cfg, shape)
    meta["model_flops_global"] = mf
    meta["model_flops_per_dev"] = mf / n_dev
    meta["useful_ratio"] = (mf / n_dev) / full["flops"] if full["flops"] else 0.0
    ideal_s = (mf / n_dev) / hw["peak_flops_bf16"]
    meta["roofline_fraction"] = ideal_s / terms.bound_s if terms.bound_s > 0 else 0.0
    return meta


ALL_SHAPES = list(SHAPES)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=ALL_SHAPES + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "pod"])
    ap.add_argument("--moe", default="tp", choices=["tp", "ep"])
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--all", action="store_true", help="run every cell via subprocesses")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--skip-cost", action="store_true",
                    help="compile + memory proof only (no cost probes)")
    ap.add_argument("--quant", default="none", choices=["none", "a2a_int8"],
                    help="int8-compressed MoE all-to-all dispatch (--moe ep)")
    ap.add_argument("--exp4", default=None,
                    help="factored Exp4 mesh 'EPxTP' e.g. 4x4 (256 chips)")
    ap.add_argument("--xent-chunk", type=int, default=0,
                    help=">0: sequence-chunked cross-entropy (train cells)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help=">1: gradient accumulation (train cells)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    if args.all:
        failures = []
        for mesh_kind in ("single", "pod"):
            for arch in ALL_ARCHS:
                for shape in ALL_SHAPES:
                    tag = f"{arch}__{shape}__{mesh_kind}__{args.moe}"
                    out_file = os.path.join(args.out, tag + ".json")
                    if os.path.exists(out_file):
                        print(f"[skip] {tag} (cached)")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                           "--moe", args.moe, "--out", args.out]
                    if mesh_kind == "pod":
                        # multi-pod pass proves the pod axis shards; the
                        # roofline table is single-pod only (spec)
                        cmd.append("--skip-cost")
                    print(f"[run ] {tag}", flush=True)
                    r = subprocess.run(cmd, capture_output=True, text=True,
                                       timeout=args.timeout)
                    if r.returncode != 0:
                        failures.append(tag)
                        print(f"[FAIL] {tag}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
        print(f"done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    res = run_cell(args.arch, args.shape, multi_pod=(args.mesh == "pod"),
                   moe_mode=args.moe, seq_shard=args.seq_shard,
                   skip_cost=args.skip_cost, quant=args.quant,
                   exp4=args.exp4, xent_chunk=args.xent_chunk,
                   microbatches=args.microbatches)
    tag = f"{args.arch}__{args.shape}__{args.mesh}__{args.moe}"
    if args.seq_shard:
        tag += "__seqshard"
    if args.quant != "none":
        tag += f"__{args.quant}"
    if args.exp4:
        tag += f"__exp4_{args.exp4}"
    if args.xent_chunk:
        tag += f"__xc{args.xent_chunk}"
    if args.microbatches > 1:
        tag += f"__mb{args.microbatches}"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
