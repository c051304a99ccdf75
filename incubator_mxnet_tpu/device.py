"""Device abstraction (parity with mxnet/device.py).

Reference: `python/mxnet/device.py:24` defines `Device(device_type, device_id)`
with `cpu()`/`gpu()` helpers and a thread-local current-device stack. The
TPU-native build maps `tpu` to jax TPU devices and keeps `gpu()` as an alias
for the accelerator so reference-style scripts run unchanged on a TPU host.

Nothing here hands back a device other than the one asked for: `tpu(i)` (and
its `gpu(i)` alias) for a chip that is not there raises, and the default
device is the CPU only in a process that was put on the CPU by name
(``JAX_PLATFORMS=cpu``, as the tests are).
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = [
    "Device",
    "Context",
    "cpu",
    "gpu",
    "tpu",
    "num_gpus",
    "num_tpus",
    "current_device",
    "memory_stats",
    "live_array_bytes",
    "gpu_memory_info",
]

_DEVTYPE_TO_JAX = {"cpu": "cpu", "tpu": "tpu", "gpu": "tpu"}


class Device:
    """A compute device: ``Device('tpu', 0)``, ``Device('cpu', 0)``.

    Usable as a context manager to set the default device, like the
    reference's ``with mx.gpu(1):`` pattern.
    """

    _default = None
    _tls = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Device):
            device_id = device_type.device_id
            device_type = device_type.device_type
        if device_type not in ("cpu", "gpu", "tpu", "cpu_pinned", "cpu_shared"):
            raise ValueError(f"unknown device type {device_type!r}")
        if device_type in ("cpu_pinned", "cpu_shared"):
            device_type = "cpu"
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- jax bridge ---------------------------------------------------------
    @property
    def jax_device(self):
        import jax

        kind = _DEVTYPE_TO_JAX[self.device_type]
        if kind == "cpu":
            devs = jax.devices("cpu")
        else:
            devs = [d for d in jax.devices() if d.platform == kind]
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self!r}: this process has {len(devs)} {kind} device(s) "
                f"(jax default backend {jax.default_backend()!r})")
        return devs[self.device_id]

    # -- protocol -----------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Device)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    def __enter__(self):
        if not hasattr(Device._tls, "stack"):
            Device._tls.stack = []
        Device._tls.stack.append(self)
        return self

    def __exit__(self, *exc):
        Device._tls.stack.pop()
        return False


# Back-compat alias, as the reference keeps `Context` (`python/mxnet/context.py`).
Context = Device


def _on_cpu_by_name() -> bool:
    """True when the process was pinned to the CPU backend explicitly
    (``JAX_PLATFORMS=cpu`` / ``jax_platforms``), as the test suite is."""
    import jax

    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


def cpu(device_id: int = 0) -> Device:
    return Device("cpu", device_id)


def tpu(device_id: int = 0) -> Device:
    return Device("tpu", device_id)


def gpu(device_id: int = 0) -> Device:
    """Alias for the accelerator device (TPU on this framework)."""
    return Device("tpu", device_id)


def num_tpus() -> int:
    import jax

    return sum(1 for d in jax.devices() if d.platform == "tpu")


num_gpus = num_tpus


def current_device() -> Device:
    stack = getattr(Device._tls, "stack", None)
    if stack:
        return stack[-1]
    if Device._default is None:
        if num_tpus():
            Device._default = tpu(0)
        elif _on_cpu_by_name():
            Device._default = cpu(0)
        else:
            raise MXNetError(
                "no TPU found, and the process was not put on the CPU by "
                "name: set JAX_PLATFORMS=cpu to run there deliberately")
    return Device._default


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes on the accelerator (reference: device.py:249);
    (0, 0) where that chip does not exist or the backend reports no stats."""
    try:
        stats = tpu(device_id).jax_device.memory_stats()
        total = stats.get("bytes_limit", 0)
        used = stats.get("bytes_in_use", 0)
        return (total - used, total)
    except Exception:
        return (0, 0)


def memory_stats(device_id: int | None = None):
    """Full allocator statistics for one device — the reference's storage
    pool counters (`src/storage/pooled_storage_manager.h` pool stats, env
    `MXNET_GPU_MEM_POOL_*`) map onto PJRT's BFC-allocator stats here:
    bytes_in_use / peak_bytes_in_use / bytes_limit / num_allocs /
    largest_alloc_size etc. Default (None) reads the CURRENT device;
    pass an id for a specific accelerator. Returns {} when the backend
    exposes none (pure-CPU platforms, some PJRT plugins)."""
    try:
        dev = current_device().jax_device if device_id is None else \
            tpu(device_id).jax_device
        return dict(dev.memory_stats() or {})
    except Exception:
        return {}


def live_array_bytes():
    """Total bytes of live jax arrays in this process — the engine-side
    view the reference exposes via per-ndarray Chunk accounting."""
    import jax

    total = 0
    for a in jax.live_arrays():
        try:
            total += a.nbytes
        except Exception:  # noqa: FL006 — deleted/donated buffer racing the sweep
            continue
    return total
