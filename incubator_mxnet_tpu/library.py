"""Custom-operator extension loader (reference:
`python/mxnet/library.py load` → C API MXLoadLib, ABI
`include/mxnet/lib_api.h`; ABI here: `src/ext/mx_ext.h`).

`load(path)` dlopens an extension library, validates the ABI version, and
registers each exported op as a callable on `incubator_mxnet_tpu.npx`.
TPU-native bridging: the C function runs on host buffers inside
`jax.pure_callback`, so extension ops work eagerly AND inside jit-compiled
(hybridized) graphs — XLA treats them as host callbacks. Forward-only
(gradients raise; write a `custom Function` for differentiable ops).
"""
from __future__ import annotations

import ctypes

import numpy as onp

__all__ = ["load"]

_DTYPE_CODES = {"float32": 0, "float64": 1, "int32": 2, "int64": 3,
                "uint8": 4, "bool": 5}
_MAX_NDIM = 8
_ABI_VERSION = 2


class _MXExtTensor(ctypes.Structure):
    _fields_ = [("dtype", ctypes.c_int),
                ("ndim", ctypes.c_int),
                ("shape", ctypes.POINTER(ctypes.c_int64)),
                ("data", ctypes.c_void_p)]


def _bind(lib):
    lib.mx_ext_abi_version.restype = ctypes.c_int
    lib.mx_ext_num_ops.restype = ctypes.c_int
    lib.mx_ext_op_name.restype = ctypes.c_char_p
    lib.mx_ext_op_name.argtypes = [ctypes.c_int]
    lib.mx_ext_op_infer_shape.restype = ctypes.c_int
    lib.mx_ext_op_infer_shape.argtypes = [
        ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
        ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int)]
    lib.mx_ext_op_forward.restype = ctypes.c_int
    lib.mx_ext_op_forward.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(_MXExtTensor),
        ctypes.POINTER(_MXExtTensor)]


def _infer_shape(lib, op_idx, in_shapes):
    n_in = len(in_shapes)
    for s in in_shapes:
        if len(s) > _MAX_NDIM:
            raise ValueError(
                f"extension ops support at most {_MAX_NDIM} dims, got "
                f"{len(s)} (the ABI's out_shape buffer is fixed-size)")
    shape_arrays = [(ctypes.c_int64 * len(s))(*s) for s in in_shapes]
    shape_ptrs = (ctypes.POINTER(ctypes.c_int64) * n_in)(
        *[ctypes.cast(a, ctypes.POINTER(ctypes.c_int64))
          for a in shape_arrays])
    ndims = (ctypes.c_int * n_in)(*[len(s) for s in in_shapes])
    out_shape = (ctypes.c_int64 * _MAX_NDIM)()
    out_ndim = ctypes.c_int()
    rc = lib.mx_ext_op_infer_shape(op_idx, n_in, shape_ptrs, ndims,
                                   out_shape, ctypes.byref(out_ndim))
    if rc != 0:
        raise ValueError(f"extension infer_shape failed (rc={rc})")
    return tuple(out_shape[i] for i in range(out_ndim.value))


def _run_forward(lib, op_idx, arrays, out_shape, out_dtype):
    n_in = len(arrays)
    keep = []  # keep ctypes shape buffers alive through the call
    tensors = (_MXExtTensor * n_in)()
    for j, a in enumerate(arrays):
        a = onp.ascontiguousarray(a)
        keep.append(a)
        shp = (ctypes.c_int64 * a.ndim)(*a.shape)
        keep.append(shp)
        tensors[j] = _MXExtTensor(
            _DTYPE_CODES[str(a.dtype)], a.ndim,
            ctypes.cast(shp, ctypes.POINTER(ctypes.c_int64)),
            a.ctypes.data_as(ctypes.c_void_p))
    out = onp.empty(out_shape, out_dtype)
    out_shp = (ctypes.c_int64 * out.ndim)(*out.shape)
    out_t = _MXExtTensor(
        _DTYPE_CODES[str(out.dtype)], out.ndim,
        ctypes.cast(out_shp, ctypes.POINTER(ctypes.c_int64)),
        out.ctypes.data_as(ctypes.c_void_p))
    rc = lib.mx_ext_op_forward(op_idx, n_in, tensors, ctypes.byref(out_t))
    if rc != 0:
        raise RuntimeError(f"extension op forward failed (rc={rc})")
    return out


def _make_op(lib, op_idx, name):
    def op(*args):
        import jax
        import jax.numpy as jnp

        from .ndarray.ndarray import NDArray, apply_op

        def jfn(*vals):
            in_shapes = [tuple(v.shape) for v in vals]
            for v in vals:
                if str(v.dtype) not in _DTYPE_CODES:
                    raise ValueError(
                        f"extension ops support dtypes "
                        f"{sorted(_DTYPE_CODES)}; got {v.dtype} — cast "
                        "inputs (e.g. .astype('float32')) before the op")
            out_shape = _infer_shape(lib, op_idx, in_shapes)
            out_dtype = onp.dtype(str(vals[0].dtype))

            def host(*host_arrays):
                return _run_forward(lib, op_idx,
                                    [onp.asarray(a) for a in host_arrays],
                                    out_shape, out_dtype)

            if any(isinstance(v, jax.core.Tracer) for v in vals):
                # inside a jit trace (hybridize): bridge via pure_callback
                return jax.pure_callback(
                    host, jax.ShapeDtypeStruct(out_shape, out_dtype), *vals)
            # eager: run the C op directly on host buffers (device→host→
            # device roundtrip, like the reference's CPU-fallback custom op)
            return jnp.asarray(host(*vals))

        wrapped = [a if isinstance(a, NDArray) else NDArray(a) for a in args]
        return apply_op(f"ext_{name}", jfn, tuple(wrapped))

    op.__name__ = name
    op.__doc__ = f"Custom extension op {name!r} (host callback; see " \
                 "library.load)."
    return op


def load(path, verbose=True):
    """Load an extension library: custom ops register on `npx`; graph
    passes and partitioners (ABI v2) register as partition backends
    applicable via `net.optimize_for(x, backend=<name>)`.
    (Reference: library.py:28 load → MXLoadLib, which registers ops,
    passes, and partitioners from the .so, lib_api.h:931-1197.)
    Returns {name: callable} for the ops."""
    import os

    if not os.path.isabs(path) and not os.path.exists(path):
        # MXNET_LIBRARY_PATH (env_var.md): search root for bare .so names
        root = os.environ.get("MXNET_LIBRARY_PATH")
        if root and os.path.exists(os.path.join(root, path)):
            path = os.path.join(root, path)
    lib = ctypes.CDLL(path)
    for sym in ("mx_ext_abi_version", "mx_ext_num_ops", "mx_ext_op_name",
                "mx_ext_op_infer_shape", "mx_ext_op_forward"):
        if not hasattr(lib, sym):
            raise ValueError(f"{path} is not a valid extension library "
                             f"(missing {sym})")
    _bind(lib)
    abi = lib.mx_ext_abi_version()
    if not 1 <= abi <= _ABI_VERSION:
        # handshake (reference lib_api.h:931 MX_LIBRARY_VERSION check):
        # newer-than-us extensions are rejected, older ones load with
        # their smaller export surface
        raise ValueError(f"extension ABI {abi} unsupported (loader "
                         f"speaks 1..{_ABI_VERSION})")
    from . import numpy_extension as npx

    ops = {}
    for i in range(lib.mx_ext_num_ops()):
        name = lib.mx_ext_op_name(i).decode()
        fn = _make_op(lib, i, name)
        ops[name] = fn
        setattr(npx, name, fn)
    backends = []
    if abi >= 2:
        backends = _register_graph_hooks(lib, path)
    if verbose:
        print(f"loaded library {path}: ops {sorted(ops)}"
              + (f", backends {backends}" if backends else ""))
    return ops


# -- ABI v2: graph passes + partitioners --------------------------------------

def _bind_v2(lib, kind):
    """Bind the optional pass/partitioner symbol triple; None if the
    library doesn't export this hook family."""
    syms = {"pass": ("mx_ext_num_passes", "mx_ext_pass_name",
                     "mx_ext_pass_apply"),
            "partitioner": ("mx_ext_num_partitioners",
                            "mx_ext_partitioner_name",
                            "mx_ext_partition")}[kind]
    try:
        num = getattr(lib, syms[0])
        name = getattr(lib, syms[1])
        apply = getattr(lib, syms[2])
        free = lib.mx_ext_free
    except AttributeError:
        return None
    num.restype = ctypes.c_int
    name.restype = ctypes.c_char_p
    name.argtypes = [ctypes.c_int]
    # returned string is extension-owned malloc memory: take it as a raw
    # pointer so WE control the copy + the mx_ext_free call
    apply.restype = ctypes.c_void_p
    apply.argtypes = [ctypes.c_int, ctypes.c_char_p]
    free.restype = None
    free.argtypes = [ctypes.c_void_p]
    return num, name, apply, free


def _call_graph_hook(apply_fn, free_fn, idx, op_names):
    import json

    graph = json.dumps(
        {"nodes": [{"id": i, "op": n} for i, n in enumerate(op_names)]})
    raw = apply_fn(idx, graph.encode())
    if not raw:
        raise RuntimeError("extension graph hook returned NULL")
    try:
        out = ctypes.string_at(raw).decode()
    finally:
        free_fn(raw)
    return json.loads(out)


class _ExtensionBackend:
    """Partition Backend whose fusion directives come from an extension
    hook at trace time (the graph they act on only exists then)."""

    mark_ops = "*"          # outline every funnel op: the extension
    patterns: list = []     # matches framework-op names, not primitives

    def __init__(self, name, apply_fn, free_fn, idx, directive_key):
        self.name = name
        self._apply = apply_fn
        self._free = free_fn
        self._idx = idx
        self._key = directive_key

    def rewrite_block(self, block, **opts):  # noqa: ARG002
        return block

    def dynamic_patterns(self, closed):
        from .partition import graph_op_names, segment_pattern

        directives = _call_graph_hook(
            self._apply, self._free, self._idx, graph_op_names(closed))
        pats = []
        for j, d in enumerate(directives.get(self._key, [])):
            pats.append(segment_pattern(
                [str(o) for o in d["ops"]],
                str(d.get("name", f"{self.name}_seg{j}"))))
        return pats


def _register_graph_hooks(lib, path):
    from .partition import register_backend

    registered = []
    for kind, key in (("pass", "fuse"), ("partitioner", "subgraphs")):
        bound = _bind_v2(lib, kind)
        if bound is None:
            continue
        num, name_fn, apply_fn, free_fn = bound
        for i in range(num()):
            raw = name_fn(i)
            if raw is None:
                raise ValueError(f"{path}: {kind} {i} has no name")
            bname = raw.decode()
            register_backend(_ExtensionBackend(bname, apply_fn, free_fn,
                                               i, key))
            registered.append(bname)
    return registered
