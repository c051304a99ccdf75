"""Which implementation an op took — decided in one place, and on record.

Every pallas site chooses between a TPU kernel and a plain-XLA expression
of the same semantics. The choice is made from what the process can observe
(the backend, the shape), never from a failure: a kernel the chip's compiler
refuses raises. Each choice is counted in the telemetry registry as
``mx_kernel_dispatch_total{op=,impl=}`` (at trace time for compiled steps,
per call for eager ops), so a run can show which branch it actually took.
"""
from __future__ import annotations

import jax

from ..telemetry import registry

__all__ = ["use_pallas", "interpret_default", "note", "choices"]

_SERIES = "mx_kernel_dispatch_total"
_COUNTERS: dict = {}   # (op, impl) -> registry counter handle


def use_pallas():
    """True where a kernel site takes its pallas branch: a TPU backend, and
    no multi-device mesh active. GSPMD cannot partition a Mosaic kernel
    ("wrap the call in a shard_map"), so a step traced under
    `DataParallel`'s mesh takes the XLA expression of the same op — chosen
    up front from the mesh, and counted as such like any other choice."""
    if jax.default_backend() != "tpu":
        return False
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    return mesh is None or mesh.devices.size == 1


def interpret_default():
    """Pallas interpret mode (or the jnp stand-in of a kernel that has no
    CPU lowering) only where the backend is the CPU — the test suite. Any
    other backend compiles the kernel for real."""
    return jax.default_backend() == "cpu"


def note(op, impl):
    c = _COUNTERS.get((op, impl))
    if c is None:
        c = _COUNTERS[(op, impl)] = registry.counter(
            _SERIES, "implementation chosen per kernel site",
            labels={"op": op, "impl": impl})
    c.inc()


def choices():
    """``{(op, impl): times chosen}`` since the registry was last reset."""
    return {k: c.value for k, c in _COUNTERS.items() if c.value}
