"""Logical-axis sharding rules with divisibility fallback.

Every parameter/activation dim carries a *logical* axis name; a RuleSet
maps logical names to ordered candidate mesh-axis assignments. A mesh axis is
assigned to a dim only if (a) it exists in the mesh, (b) it is not already
used by another dim of the same tensor, and (c) its size divides the dim.
Assignment order follows per-name priority (e.g. kv_heads outranks kv_seq, so
a GQA tensor shards heads first and falls back to sequence sharding only when
the head count doesn't divide — the flash-decode style layout).

This is how qwen's kv=2 ends up replicated across model=16 while gemma2's
kv=16 shards exactly, with zero per-arch code.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import ParamSpec

Candidate = Tuple[str, ...]          # one candidate = tuple of mesh axes used together


@dataclass(frozen=True)
class RuleSet:
    rules: Dict[str, Tuple[Candidate, ...]]
    priority: Dict[str, int]
    name: str = "custom"

    def candidates(self, logical: str) -> Tuple[Candidate, ...]:
        return self.rules.get(logical, ())

    def prio(self, logical: str) -> int:
        return self.priority.get(logical, 0)


_PRIORITY = {
    "experts": 10, "heads": 10, "kv_heads": 10,
    "expert_mlp": 9, "mlp": 9, "vocab": 9, "ssm_proj": 9, "ssm_inner": 9,
    "conv_dim": 9, "ssm_heads": 9,
    "batch": 8,
    "embed": 5,
    "kv_seq": 3, "seq": 2,
    "layers": 0, "head_dim": 0, "vision_patch": 0,
}


def make_rules(mode: str = "serve", moe: str = "ep", *, multi_pod: bool = False,
               seq_shard: bool = False, tensor_axis: str = "model",
               expert_axis: Optional[str] = None) -> RuleSet:
    """mode: "serve" | "train".  moe: "ep" (hybrid TPxEP — experts on the
    expert axis, the paper's optimized config) | "tp" (paper-baseline pure TP
    — experts replicated). On the fixed production mesh the expert axis IS
    the data axis; the factored Exp4 mesh ("data","expert","tensor") names
    them explicitly."""
    t = tensor_axis
    e = expert_axis or "data"
    if multi_pod:
        batch: Tuple[Candidate, ...] = (("pod", "data"), ("data",))
    else:
        batch = (("data",),)
    r: Dict[str, Tuple[Candidate, ...]] = {
        "batch": batch,
        "heads": ((t,),),
        "kv_heads": ((t,),),
        "mlp": ((t,),),
        "expert_mlp": ((t,),),
        "vocab": ((t,),),
        "experts": ((e,),) if moe == "ep" else (),
        # kv_seq: fallback when kv_heads can't take the tensor axis; on the
        # factored Exp4 mesh it may also spill onto the expert axis
        "kv_seq": ((t,), (e,)) if expert_axis else ((t,),),
        "ssm_proj": ((t,),),
        "ssm_inner": ((t,),),
        "conv_dim": ((t,),),
        "ssm_heads": ((t,),),
    }
    if mode == "train":
        r["embed"] = (("data",),)             # FSDP within pod
    if seq_shard:
        r["seq"] = ((t,),)                    # sequence parallelism (hillclimb)
    return RuleSet(rules=r, priority=dict(_PRIORITY), name=f"{mode}/{moe}/{t}")


def partition_spec(shape: Sequence[int], logical: Sequence[Optional[str]],
                   mesh: Mesh, ruleset: RuleSet) -> P:
    """Build a PartitionSpec for `shape` with divisibility + axis-reuse checks."""
    assert len(shape) == len(logical), (shape, logical)
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assign: Dict[int, Tuple[str, ...]] = {}
    used: set = set()
    order = sorted(range(len(shape)),
                   key=lambda i: -ruleset.prio(logical[i]) if logical[i] else 1)
    for i in order:
        name = logical[i]
        if name is None:
            continue
        for cand in ruleset.candidates(name):
            if any(a not in mesh_sizes for a in cand):
                continue
            if any(a in used for a in cand):
                continue
            prod = int(np.prod([mesh_sizes[a] for a in cand]))
            if prod > 1 and shape[i] % prod == 0:
                assign[i] = cand
                used.update(cand)
                break
    entries = []
    for i in range(len(shape)):
        if i in assign:
            entries.append(assign[i] if len(assign[i]) > 1 else assign[i][0])
        else:
            entries.append(None)
    return P(*entries)


# --------------------------------------------------------------------------
# Active context (thread-local): installs (mesh, ruleset) so model code can
# call ``constrain`` without threading sharding through every function.
# --------------------------------------------------------------------------
class _Active(threading.local):
    mesh: Optional[Mesh] = None
    ruleset: Optional[RuleSet] = None


_active = _Active()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], ruleset: Optional[RuleSet]):
    prev = (_active.mesh, _active.ruleset)
    _active.mesh, _active.ruleset = mesh, ruleset
    try:
        yield
    finally:
        _active.mesh, _active.ruleset = prev


def constrain(x, logical: Sequence[Optional[str]]):
    if _active.mesh is None or _active.ruleset is None:
        return x
    spec = partition_spec(x.shape, logical, _active.mesh, _active.ruleset)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_active.mesh, spec))


# --------------------------------------------------------------------------
# Whole-tree spec builders
# --------------------------------------------------------------------------
def param_partition_specs(spec_tree, mesh: Mesh, ruleset: RuleSet):
    """ParamSpec tree -> PartitionSpec tree."""
    return jax.tree.map(
        lambda s: partition_spec(s.shape, s.logical, mesh, ruleset),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
