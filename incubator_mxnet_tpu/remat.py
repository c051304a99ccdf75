"""Activation rematerialization (memory-opt) policies.

Reference: `MXNET_BACKWARD_DO_MIRROR` (mirror almost all activations —
recompute them in backward) and `MXNET_MEMORY_OPT` (the graph memory
optimizer) — `docs/static_site/src/pages/api/faq/env_var.md:230-238`,
implemented by the nnvm mirror pass (`src/nnvm/gradient.cc`).

TPU-native: the same trade is `jax.checkpoint` over the compiled forward —
the backward recomputes from checkpointed inputs instead of holding every
activation to the end of the step. The `policy` argument picks WHAT may be
saved (jax.checkpoint_policies):

- ``remat=True`` / ``"nothing_saveable"``: save nothing, recompute
  everything — the DO_MIRROR semantic.
- ``"dots_saveable"``: save matmul/conv outputs (MXU work), recompute
  elementwise/VPU ops — the balanced MEMORY_OPT semantic.
- any other `jax.checkpoint_policies` name, or a policy callable.

Environment parity: setting ``MXNET_BACKWARD_DO_MIRROR=1`` or
``MXNET_MEMORY_OPT=1`` applies the corresponding default to every
`hybridize()` / `DataParallel` that doesn't pass ``remat`` explicitly.

Measurement: `saved_bytes(fn, *args)` sums the autodiff residuals a
function would keep live between forward and backward — the quantity
remat controls. (Final HBM peaks are XLA's call, made per backend at
compile time, so the residual ledger is the framework-level contract we
can pin.)
"""
from __future__ import annotations

import os

__all__ = ["resolve_policy", "wrap", "saved_bytes"]

_TRUE = ("1", "true", "yes", "on")


def resolve_policy(spec):
    """Normalize a remat spec to (active, policy-or-None).

    spec: None (consult env), False (off), True (nothing_saveable),
    a policy name string, or a callable policy."""
    if spec is None:
        if os.environ.get("MXNET_BACKWARD_DO_MIRROR", "").lower() in _TRUE:
            spec = True
        elif os.environ.get("MXNET_MEMORY_OPT", "").lower() in _TRUE:
            spec = "dots_saveable"
        else:
            return False, None
    if spec is False:
        return False, None
    import jax

    if spec is True:
        return True, jax.checkpoint_policies.nothing_saveable
    if callable(spec):
        return True, spec
    policy = getattr(jax.checkpoint_policies, str(spec), None)
    if policy is None:
        raise ValueError(
            f"unknown remat policy {spec!r}; see jax.checkpoint_policies")
    return True, policy


def wrap(fn, spec):
    """jax.checkpoint-wrap `fn` per the resolved spec (identity if off)."""
    active, policy = resolve_policy(spec)
    if not active:
        return fn
    import jax

    return jax.checkpoint(fn, policy=policy)


def saved_bytes(fn, *args):
    """Total bytes of autodiff residuals `fn` saves for backward — the
    live forward→backward memory the remat policy governs."""
    # no public alias in the installed jax (0.9.0)
    from jax._src.ad_checkpoint import saved_residuals

    total = 0
    for aval, _src in saved_residuals(fn, *args):
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for s in shape:
            n *= s
        total += n * dtype.itemsize
    return total
