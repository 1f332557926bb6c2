"""Production meshes. Functions, not module constants — importing this module
never touches jax device state.

Single pod : (16, 16)    axes ("data", "model")   = 256 chips (TPU v5e pod)
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

The "pod" axis carries data-parallel replica groups (requests are pod-local;
only gradient all-reduce / checkpoint distribution crosses pods — DCN, not
ICI). The Exp4 factored mesh exposes expert x tensor explicitly for the
paper's EP/TP sweep.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_moe_mesh(ep: int, tp: int, *, chips: int = 256):
    """Factored Exp4 mesh: ("data", "expert", "tensor"). ep*tp must divide
    chips; the rest is data parallelism. e.g. (EP4, TP2) on 8 chips per the
    paper's DGX box, or EP x TP tiles of a 256-chip pod."""
    assert chips % (ep * tp) == 0, (chips, ep, tp)
    return _mk((chips // (ep * tp), ep, tp), ("data", "expert", "tensor"))


def make_dev_mesh(data: int = 1, model: int = 1):
    """Small mesh for CPU tests."""
    return _mk((data, model), ("data", "model"))
