#!/usr/bin/env python3
"""chipbench/run.py — one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. It refuses anything but a TPU
with the chips the cell asks for (exit code 2, no result line), makes weights
and traffic from ``--seed``, warms the cell's own shapes (set-up), measures for
``--seconds``, frees the program, compares what the window produced with the
plain reference, and prints the result as the last line of stdout. With
``--trace 1`` the profiler runs over the first `trace_s` seconds of the window
and the line carries the cell's per-layer metrics and a breakdown instead of
the end-to-end metrics.

Everything that belongs to one cell, configuration, traffic mix or metric is
a file of its own under chipbench/ (see chipbench/README.md); this file knows
none of them by name.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.lib import harness  # noqa: E402


class Env:
    """What a runner is handed: the cell's files, the arguments, the devices,
    and the hooks that mark where set-up ends and the window opens."""

    def __init__(self, spec, args, devices, trace_dir):
        self.spec, self.devices = spec, devices
        self.seed, self.seconds, self.trace = args.seed, args.seconds, args.trace
        self.watch = harness.CompileWatch()
        self.spans = harness.Spans()
        self.tracer = harness.Tracer(
            trace_dir, min(spec.traffic["trace_s"], args.seconds),
            self.spans) if args.trace else None
        self.setup_s = None
        self.at_open = None

    def mark(self, what):
        """Where set-up's seconds go: a line on stdout per phase."""
        print(f"chipbench setup {time.perf_counter() - T_PROCESS:8.2f} s  "
              f"{what}", flush=True)

    def open_window(self):
        """Called by the runner at the window's start: set-up ends here."""
        self.at_open = self.watch.snapshot()
        if self.tracer is not None:
            self.tracer.start()
        self.setup_s = time.perf_counter() - T_PROCESS
        self.mark("window opens")


def measure(names, spec, obs):
    """Each named metric through its reader; a reader with nothing to read
    returns None and the metric is left out."""
    out = {}
    for name in names:
        m = spec.metric(name)
        reader = harness.module_of("readers", m["reader"], spec.root)
        value = reader.read(obs, **m.get("params", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(args, root=harness.CHIPBENCH, require_tpu=True):
    spec = harness.Spec(args.workload, root)
    harness.configure_compile_cache()
    devices = harness.find_devices(spec.cell["chips"], require_tpu)
    trace_dir = os.path.join(
        os.environ.get("TMPDIR") or os.path.join(harness.CHECKOUT, ".cb_tmp"),
        f"chipbench_trace_{os.getpid()}")
    env = Env(spec, args, devices, trace_dir)
    env.mark("jax up, devices found")
    runner = harness.module_of("runners", spec.config["runner"], spec.root)
    try:
        got = runner.run(env)
        trace = None
        if env.tracer is not None:
            from chipbench.lib.trace import Trace

            lanes = env.tracer.finish()
            if os.environ.get("CHIPBENCH_KEEP_LANES"):   # recording testdata
                from chipbench.lib.trace import write_lanes

                write_lanes(lanes, os.environ["CHIPBENCH_KEEP_LANES"])
            trace = Trace(lanes)
            print("chipbench programs in trace:", trace.module_names()[:12],
                  flush=True)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    dev = devices[0]
    print("chipbench window:", json.dumps(
        {k: v for k, v in got["window"].items()
         if not isinstance(v, list) or len(v) <= 12}), flush=True)
    obs = dict(got, spans=env.spans, trace=trace, spec=spec, chips=len(devices),
               peak=spec.peak(dev.device_kind) if dev.platform == "tpu" else None,
               setup_s=env.setup_s, watch_setup=env.at_open,
               trace_clock=env.tracer.clock if env.tracer else None,
               flops=harness.module_of("flops", spec.config["family"], spec.root))
    result = {
        "correct": harness.all_passed(got["checks"]),
        "attempted": got["attempted"], "failed": got["failed"],
        "metrics": measure(spec.cell["per_layer" if args.trace else "end_to_end"],
                           spec, obs),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": got["memory_peak_bytes"]},
    }
    if trace is not None:
        result["device"].update(busy_s=trace.busy_s(), window_s=trace.window_s)
        result["breakdown"] = {"device_ops": trace.top_ops(),
                               "idle_gaps": trace.idle_gaps()}
    result["notes"] = {"setup": env.at_open,
                       "in_window": got["compiled_in_window"],
                       "wall_s": got["window"]["wall_s"]}
    harness.report(result, got["checks"])
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    run_cell(ap.parse_args(argv))


if __name__ == "__main__":
    main()
