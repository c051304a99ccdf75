"""Collective ops over the mesh (replacement for `src/kvstore/comm.h` reduce
trees and NCCL/ps-lite: `psum`/`all_gather`/`ppermute` ride ICI links and XLA
overlaps them with compute — the latency-hiding the reference built P3 for).

These are meant to be called INSIDE a shard_map'ed/pjit'ed function; thin
wrappers around jax.lax so user code never imports jax directly. They are
also the fleet profiler's census point: when `telemetry.fleet` is enabled,
every wrapper reports its op/axis/payload-bytes through the module-global
`_CENSUS` hook (a trace-time count — host wall time inside a traced body
would measure tracing, not execution; `fleet.probe_collectives` owns honest
per-op seconds). Lint FL014 keeps raw `lax` collectives in `parallel/` and
`serve/` routed through here so the census can't be bypassed."""
from __future__ import annotations

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "broadcast",
           "ring_permute", "all_to_all", "axis_size", "pvary"]


def pvary(x, axis_name):
    """Mark a value device-varying over `axis_name` — shard_map's
    replication-typing escape hatch for loop carries whose body outputs
    are varying (ppermute/axis_index inside): `jax.lax.pcast(...,
    to="varying")`. Not a comms op, so no census."""
    import jax

    from ..ndarray.ndarray import NDArray

    v = x._data if isinstance(x, NDArray) else x
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    out = jax.lax.pcast(v, names, to="varying")
    return NDArray(out) if isinstance(x, NDArray) else out


def axis_size(axis_name):
    """Static size of a mapped axis (a Python int inside shard_map/pjit).
    Not a comms op, so no census."""
    import jax

    return jax.lax.axis_size(axis_name)


def all_reduce(x, axis_name, op="sum"):
    import jax

    from ..ndarray.ndarray import NDArray

    v = x._data if isinstance(x, NDArray) else x
    c = _CENSUS
    if c is not None:
        c("all_reduce", axis_name, v)
    if op == "sum":
        out = jax.lax.psum(v, axis_name)
    elif op == "mean":
        out = jax.lax.pmean(v, axis_name)
    elif op == "max":
        out = jax.lax.pmax(v, axis_name)
    elif op == "min":
        out = jax.lax.pmin(v, axis_name)
    else:
        raise ValueError(f"unknown op {op!r}")
    return NDArray(out) if isinstance(x, NDArray) else out


def all_gather(x, axis_name, axis=0, tiled=True):
    import jax

    from ..ndarray.ndarray import NDArray

    v = x._data if isinstance(x, NDArray) else x
    c = _CENSUS
    if c is not None:
        c("all_gather", axis_name, v)
    out = jax.lax.all_gather(v, axis_name, axis=axis, tiled=tiled)
    return NDArray(out) if isinstance(x, NDArray) else out


def reduce_scatter(x, axis_name, axis=0):
    import jax

    from ..ndarray.ndarray import NDArray

    v = x._data if isinstance(x, NDArray) else x
    c = _CENSUS
    if c is not None:
        c("reduce_scatter", axis_name, v)
    out = jax.lax.psum_scatter(v, axis_name, scatter_dimension=axis, tiled=True)
    return NDArray(out) if isinstance(x, NDArray) else out


def broadcast(x, axis_name, src=0):
    import jax

    from ..ndarray.ndarray import NDArray

    v = x._data if isinstance(x, NDArray) else x
    c = _CENSUS
    if c is not None:
        c("broadcast", axis_name, v)
    idx = jax.lax.axis_index(axis_name)
    mask = (idx == src).astype(v.dtype)
    out = jax.lax.psum(v * mask, axis_name)
    return NDArray(out) if isinstance(x, NDArray) else out


def ring_permute(x, axis_name, shift=1):
    """Send each shard to the next device on the ring (the building block of
    ring attention / ring allreduce; rides neighbor ICI links)."""
    import jax

    from ..ndarray.ndarray import NDArray

    v = x._data if isinstance(x, NDArray) else x
    c = _CENSUS
    if c is not None:
        c("ring_permute", axis_name, v)
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    out = jax.lax.ppermute(v, axis_name, perm)
    return NDArray(out) if isinstance(x, NDArray) else out


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    """Expert-parallel dispatch/return primitive: every device scatters
    `split_axis` slices to its peers and concatenates what it receives
    along `concat_axis` (the MoE all-to-all; see `parallel/moe.py`)."""
    import jax

    from ..ndarray.ndarray import NDArray

    v = x._data if isinstance(x, NDArray) else x
    c = _CENSUS
    if c is not None:
        c("all_to_all", axis_name, v)
    out = jax.lax.all_to_all(v, axis_name, split_axis=split_axis,
                             concat_axis=concat_axis, tiled=tiled)
    return NDArray(out) if isinstance(x, NDArray) else out


_CENSUS = None   # armed by telemetry.fleet.enable(): (op, axis, value) hook


def _rearm_hooks():
    import sys

    fleet = sys.modules.get(__name__.rsplit(".", 2)[0] + ".telemetry.fleet")
    if fleet is not None and fleet.is_enabled():
        fleet._arm()


_rearm_hooks()
