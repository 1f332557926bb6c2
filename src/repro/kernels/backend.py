"""One place that maps the platform to a kernel backend.

``backend="auto"`` means the Pallas kernel on a TPU and the XLA reference
elsewhere. ``interpret=None`` means compiled on a TPU and interpreted
elsewhere; tests that want the Pallas interpreter pass ``interpret=True``.
A backend that fails to start raises here: there is no silent CPU fallback.
"""
from __future__ import annotations

import re

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(backend: str, interpret: bool | None) -> tuple[str, bool]:
    """(backend, interpret) with "auto" and None worked out from the platform."""
    if backend == "auto":
        backend = "pallas" if on_tpu() else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown kernel backend {backend!r}")
    if interpret is None:
        interpret = not on_tpu()
    return backend, interpret


def compiled_kernels(hlo_text: str) -> list[str]:
    """Names of the Pallas kernels a compiled TPU program holds as Mosaic
    custom calls, one entry per call site (e.g. ``gmm_pallas``). An
    interpreted kernel lowers to plain HLO and does not appear."""
    return re.findall(r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*'
                      r'custom_call_target="tpu_custom_call"', hlo_text)
