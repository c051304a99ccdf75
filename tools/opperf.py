#!/usr/bin/env python
"""opperf — per-operator micro-benchmark harness
(reference: `benchmark/opperf/opperf.py` — runs every op with standard
inputs and reports forward/backward latency).

Measures the FRAMEWORK path (NDArray funnel → jit cache → device), not raw
jax, so dispatch overhead is included — the number a user's eager code sees.

Usage:
    python tools/opperf.py                  # default op set, JSON to stdout
    python tools/opperf.py --ops dot,relu --shape 1024,1024
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _onp():
    import numpy

    return numpy


def _ops_registry():
    from incubator_mxnet_tpu import np, npx

    def u(*shape):
        return np.random.uniform(size=shape, low=-1.0, high=1.0)

    # op name -> (fn, args-thunk); shapes chosen per reference opperf defaults
    return {
        "add": (lambda a, b: a + b, lambda s: (u(*s), u(*s))),
        "mul": (lambda a, b: a * b, lambda s: (u(*s), u(*s))),
        "dot": (np.dot, lambda s: (u(*s), u(*s))),
        "exp": (np.exp, lambda s: (u(*s),)),
        "log": (lambda x: np.log(np.abs(x) + 1e-3), lambda s: (u(*s),)),
        "sum": (np.sum, lambda s: (u(*s),)),
        "mean": (np.mean, lambda s: (u(*s),)),
        "relu": (npx.relu, lambda s: (u(*s),)),
        "sigmoid": (npx.sigmoid, lambda s: (u(*s),)),
        "softmax": (npx.softmax, lambda s: (u(*s),)),
        "fully_connected": (
            lambda x, w, b: npx.fully_connected(x, w, b,
                                                num_hidden=w.shape[0]),
            lambda s: (u(*s), u(s[-1], s[-1]), u(s[-1]))),
        "batch_norm": (
            lambda x, g, b, m, v: npx.batch_norm(x, g, b, m, v),
            lambda s: (u(*s), np.ones((s[1],)), np.zeros((s[1],)),
                       np.zeros((s[1],)), np.ones((s[1],)))),
        "transpose": (lambda x: x.T, lambda s: (u(*s),)),
        "concat": (lambda a, b: np.concatenate([a, b]),
                   lambda s: (u(*s), u(*s))),
    }


def _sync(x):
    """Wait for the LAST output: the device stream executes in order, so
    `block_until_ready` on it fences every enqueued program."""
    v = x
    while isinstance(v, (list, tuple)):
        v = v[0]
    if hasattr(v, "wait_to_read"):
        v.wait_to_read()
    else:
        v.block_until_ready()


def benchmark_op(name, fn, args, warmup=5, runs=50, with_backward=True):
    from incubator_mxnet_tpu import autograd

    for a in args:
        a.attach_grad()
    out = None
    for _ in range(warmup):
        out = fn(*args)
    if out is not None:
        _sync(out)
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*args)
    _sync(out)
    fwd_ms = (time.perf_counter() - t0) / runs * 1e3

    bwd_ms = None
    if with_backward:
        try:
            for _ in range(warmup):
                with autograd.record():
                    out = fn(*args)
                out.backward()
            _sync(args[0].grad)
            t0 = time.perf_counter()
            for _ in range(runs):
                with autograd.record():
                    out = fn(*args)
                out.backward()
            _sync(args[0].grad)
            total_ms = (time.perf_counter() - t0) / runs * 1e3
            # derived bwd = total - fwd; dispatch noise can make the
            # subtraction non-positive — report the MEASURED total and
            # leave bwd null instead of publishing a fake 0.0 cell
            bwd_ms = total_ms - fwd_ms if total_ms > fwd_ms else None
        except Exception:  # op has no grad path
            total_ms = None
            bwd_ms = None
    else:
        total_ms = None
    return {"op": name, "avg_fwd_ms": round(fwd_ms, 4),
            "avg_bwd_ms": round(bwd_ms, 4) if bwd_ms is not None else None,
            "avg_fwdbwd_ms": round(total_ms, 4)
            if total_ms is not None else None}


def benchmark_op_compiled(name, fn, args, warmup=3, runs=30):
    """Compiled-op cost: jit the op once, execute `runs` times, and read
    the per-call DEVICE time from the profiler's XPlane timeline.

    Rationale: this framework's execution model is compiled (hybridize /
    jit), and an eager per-op wall time is mostly host dispatch, which
    measures the funnel, not the op. The reference's opperf numbers are meaningful eagerly because its
    engine dispatches precompiled kernels in-process; the compiled-mode
    device number is the apples-to-apples one here."""
    import jax

    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    vals = [a._data for a in args]

    @jax.jit
    def jfn(*vs):
        out = fn(*[NDArray(v) for v in vs])
        first = out
        while isinstance(first, (list, tuple)):
            first = first[0]
        return first._data

    out = None
    for _ in range(warmup):
        out = jfn(*vals)
    out.block_until_ready()
    profiler.dumps(reset=True)
    profiler.start()
    t0 = time.perf_counter()
    for _ in range(runs):
        out = jfn(*vals)
    out.block_until_ready()
    wall_ms = (time.perf_counter() - t0) / runs * 1e3
    profiler.stop()
    # the jitted program's umbrella event on the device lane IS the per-op
    # device cost (its children would double-count)
    evts = profiler.device_events()
    lanes = {e["pid"]: e.get("args", {}).get("name", "")
             for e in evts if e.get("ph") == "M"
             and e.get("name") == "process_name"}
    dev_us = 0.0
    n_seen = 0
    for e in evts:
        if e.get("ph") == "X" and e.get("name", "").startswith("jit_jfn") \
                and lanes.get(e.get("pid"), "").startswith("/device:"):
            dev_us += float(e.get("dur", 0.0))
            n_seen += 1
    profiler.dumps(reset=True)
    device_ms = (dev_us / n_seen / 1000.0) if n_seen else None
    return {"op": name,
            "device_ms": round(device_ms, 4) if device_ms is not None
            else None,
            "wall_ms": round(wall_ms, 4)}


def anchor_configs():
    """The BASELINE.md anchor rows (exact reference opperf shapes —
    `benchmark/opperf/results/mxnet_operator_benchmark_results_{cpu,gpu}.md`)
    plus a conv2d serving shape."""
    from incubator_mxnet_tpu import np, npx

    def u(*shape):
        return np.random.uniform(size=shape, low=-1.0, high=1.0)

    return {
        "dot_1024x1024": (np.dot, lambda: (u(1024, 1024), u(1024, 1024))),
        "fully_connected_32x3x256x256_h64": (
            lambda x, w, b: npx.fully_connected(x, w, b, num_hidden=64),
            lambda: (u(32, 3, 256, 256), u(64, 3 * 256 * 256), u(64))),
        "softmax_1024x1024": (npx.softmax, lambda: (u(1024, 1024),)),
        "batch_norm_32x3x256x256": (
            lambda x, g, b, m, v: npx.batch_norm(x, g, b, m, v),
            lambda: (u(32, 3, 256, 256), np.ones((3,)), np.zeros((3,)),
                     np.zeros((3,)), np.ones((3,)))),
        "conv1d_32x3x256_k3_f64": (
            lambda x, w, b: npx.convolution(x, w, b, kernel=(3,),
                                            num_filter=64),
            lambda: (u(32, 3, 256), u(64, 3, 3), u(64))),
        "conv2d_32x3x224x224_k3_f64": (
            lambda x, w, b: npx.convolution(x, w, b, kernel=(3, 3),
                                            num_filter=64),
            lambda: (u(32, 3, 224, 224), u(64, 3, 3, 3), u(64))),
        "sum_1024x1024": (lambda x: x.sum(), lambda: (u(1024, 1024),)),
        # anchors the model corpus actually leans on (round-4 additions)
        "pooling_max_32x64x56x56_k2s2": (
            lambda x: npx.pooling(x, kernel=(2, 2), stride=(2, 2),
                                  pool_type="max"),
            lambda: (u(32, 64, 56, 56),)),
        "layer_norm_8192x768": (
            lambda x, g, b: npx.layer_norm(x, g, b),
            lambda: (u(8192, 768), np.ones((768,)), np.zeros((768,)))),
        "embedding_8192_30522x768": (
            lambda idx, w: npx.embedding(idx, w, input_dim=30522,
                                         output_dim=768),
            lambda: (np.array(_onp().random.RandomState(0)
                              .randint(0, 30522, (64, 128))
                              .astype("float32")), u(30522, 768))),
        "flash_attention_8x12x128x64": (
            lambda q, k, v: npx.flash_attention(q, k, v),
            lambda: (u(8, 12, 128, 64), u(8, 12, 128, 64),
                     u(8, 12, 128, 64))),
    }


def run_anchor_benchmarks(warmup=5, runs=50, mode="eager"):
    results = []
    for name, (fn, make_args) in anchor_configs().items():
        if mode == "compiled":
            results.append(benchmark_op_compiled(name, fn, make_args(),
                                                 min(warmup, 3), runs))
        else:
            results.append(benchmark_op(name, fn, make_args(), warmup, runs))
    return results


def run_performance_test(ops=None, shape=(1024, 1024), warmup=5, runs=50):
    """Benchmark `ops` (all by default) at `shape`; returns list of dicts
    (reference: benchmark/opperf/opperf.py run_op_benchmarks)."""
    registry = _ops_registry()
    names = ops or list(registry)
    results = []
    for name in names:
        if name not in registry:
            raise ValueError(f"unknown op {name!r}; known: {sorted(registry)}")
        fn, make_args = registry[name]
        try:
            args = make_args(tuple(shape))
        except Exception as e:  # shape unsupported for this op
            results.append({"op": name, "error": str(e)})
            continue
        results.append(benchmark_op(name, fn, args, warmup, runs))
    return results


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ops", default=None,
                   help="comma-separated op names (default: all)")
    p.add_argument("--shape", default="1024,1024")
    p.add_argument("--runs", type=int, default=50)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--output", default=None, help="write JSON here")
    p.add_argument("--anchors", action="store_true",
                   help="run the BASELINE.md anchor-row configs instead")
    p.add_argument("--mode", default="eager", choices=("eager", "compiled"),
                   help="eager: NDArray funnel dispatch latency; compiled: "
                        "jitted per-op DEVICE time from the profiler")
    args = p.parse_args()

    if args.anchors:
        results = run_anchor_benchmarks(args.warmup, args.runs, args.mode)
        out = json.dumps({"anchors": True, "mode": args.mode,
                          "results": results}, indent=2)
    else:
        shape = tuple(int(s) for s in args.shape.split(","))
        ops = args.ops.split(",") if args.ops else None
        results = run_performance_test(ops, shape, args.warmup, args.runs)
        out = json.dumps({"shape": list(shape), "results": results}, indent=2)
    if args.output:
        with open(args.output, "w") as f:
            f.write(out)
    print(out)


if __name__ == "__main__":
    main()
