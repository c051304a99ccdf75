#!/usr/bin/env python3
"""chipbench/control.py — the readings a cell's limits are set from.

Not part of a benchmark run (the driver never calls it). On the chip, at the
cell's own size, in one process over many seeds:

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3,... \
        [--control-seeds 3] [--seconds 12]

* every seed: the program's numbers against the reference (the lower reading
  is the largest of them);
* the first `--control-seeds` seeds: the control — the reference put in the
  program's place with int8 matmul inputs, the precision below the bfloat16
  products that both configurations state (for a served model read at every
  position of the sampled prompts and tokens) — and, for a training cell,
  the planted faults (half of the batch left out and the mean taken over the
  rest; on a mesh, one chip's quarter alone: the exchange left out). The
  upper reading is the smallest the control gives.

Every line is judged by the harness's own comparison (`harness.passed`) at
the limits of the cell's file and says `correct`: the program's lines have to
read true, the control's and the faults' false.

`--sweep r1,r2,...` instead runs an open-loop cell's traffic at each arrival
rate against one engine, a window of `--seconds` each with as many requests
as the rate puts into it, and prints what it sustained: how the knee of
`gpt2xl.chat` was found.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.lib import harness  # noqa: E402
from chipbench.lib.trace import percentile  # noqa: E402


def say(checks=None, **kw):
    """One reading; with `checks`, each compared number and the verdict the
    harness gives them at the cell's limits."""
    if checks is not None:
        kw.update({c["name"]: c["value"] for c in checks})
        kw["failed"] = [c["name"] for c in checks if not harness.passed(c)]
        kw["correct"] = harness.all_passed(checks)
    print("CONTROL " + json.dumps(kw), flush=True)


def as_program(readings, names):
    import numpy as onp

    return {"loss": readings["loss"],
            "grad": [readings["grad"][n] for n in names],
            "grad_norm": onp.asarray([readings["grad_norm"][n] for n in names]),
            "delta_norm": onp.asarray([readings["delta_norm"][n] for n in names])}


def train(spec, devices, seeds, n_control):
    from chipbench.runners import train as tr

    limits = spec.cell["limits"]
    for i, seed in enumerate(seeds):
        prog, names, rows = tr.program_readings(spec, seed, devices,
                                                harness.Spans())
        seq = spec.traffic["seq"]
        ref = tr.reference_readings(spec, seed, rows, seq, devices=devices)
        say(tr.judged(tr.compare(prog, ref, names), limits)["list"],
            seed=seed, side="program")
        if i >= n_control:
            continue
        others = {"control_int8": {"matmul": "int8"},
                  "fault_half_batch": {"part": slice(0, rows // 2)}}
        if len(devices) > 1:
            others["fault_no_exchange"] = {
                "part": slice(0, rows // len(devices))}
        for side, how in others.items():
            bad = tr.reference_readings(spec, seed, rows, seq, devices=devices,
                                        **how)
            say(tr.judged(tr.compare(as_program(bad, names), ref, names),
                          limits)["list"], seed=seed, side=side)


def quiet_env(spec, devices, seed, seconds):
    from chipbench import run as entry

    env = entry.Env(spec, argparse.Namespace(seed=seed, seconds=seconds,
                                             trace=0), devices, None)
    env.mark = lambda what: None
    return env


def serve(spec, devices, seeds, n_control, seconds):
    from chipbench.runners import serve as sv

    limits = spec.cell["limits"]
    for i, seed in enumerate(seeds):
        got = sv.run(quiet_env(spec, devices, seed, seconds))
        say(got["checks"], seed=seed, side="program")
        if i >= n_control or not got["sample"]:
            continue
        ref, _ = sv.reference_logits(spec, seed, got["sample"],
                                     everywhere=True)
        low, _ = sv.reference_logits(spec, seed, got["sample"], "int8",
                                     everywhere=True)
        say(sv.gap_checks(sv.gaps(ref, low.argmax(-1)), limits), seed=seed,
            side="control_int8", positions=len(ref),
            tokens_differ=float((low.argmax(-1) != ref.argmax(-1)).mean()))


def sweep(spec, devices, rates, seeds, seconds):
    """One engine, the cell's traffic at each rate and seed in turn; between
    two windows every request is waited for, so each starts on empty slots."""
    from chipbench.lib import loadgen
    from chipbench.runners import serve as sv

    env = quiet_env(spec, devices, seeds[0], seconds)
    live = sv.start(env)
    try:
        for rate in rates:
            for seed in seeds:
                n = max(1, round(rate * seconds))
                traffic = dict(spec.traffic, sizes=n, rate_rps=n / seconds,
                               ramp_sizes=max(1, round(
                                   rate * spec.traffic["ramp_s"])))
                got = sv.window(env, live, traffic, seed)
                got.client.join(120.0)
                w, asked = got.readings, loadgen.size_set(traffic)
                first = sorted((r.due, r.token_times[0] - r.due)
                               for r in got.due if r.token_times)
                third = max(1, len(first) // 3)
                busy = sum(max(0.0, min(r.token_times[-1], w["t_close"])
                               - max(r.token_times[0], w["t_open"]))
                           for r in got.client.sent if r.token_times)
                say(rate_rps=n / seconds, seed=seed, due=len(got.due),
                    failed=len(got.failed), finished=w["requests_finished"],
                    out_tokens_s=w["out_tokens"] / w["wall_s"],
                    asked_tokens_s=sum(o for _, o, _ in asked) / seconds,
                    slots_streaming_mean=busy / w["wall_s"],
                    ttft_p50_ms_by_third=[
                        percentile([t * 1e3 for _, t in part], 50)
                        for part in (first[:third], first[third:2 * third],
                                     first[2 * third:])],
                    ttft_p50_ms=percentile(w["ttft_ms"], 50),
                    ttft_p90_ms=percentile(w["ttft_ms"], 90),
                    submit_wait_p50_ms=percentile(w["submit_wait_ms"], 50),
                    itl_p50_ms=percentile(w["itl_ms"], 50),
                    itl_p95_ms=percentile(w["itl_ms"], 95),
                    itl_over_200ms=w["itl_share_over_ms"]["200"])
    finally:
        live.eng.shutdown(drain=False)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--root", default=harness.CHIPBENCH,
                    help="another directory of cell files (tests)")
    ap.add_argument("--any-device", action="store_true",
                    help="rehearse the control flow off the chip (tests)")
    args = ap.parse_args(argv)
    spec = harness.Spec(args.workload, args.root)
    harness.configure_compile_cache()
    devices = harness.find_devices(spec.cell["chips"], not args.any_device)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.sweep:
        sweep(spec, devices, [float(r) for r in args.sweep.split(",")],
              seeds, args.seconds)
    elif spec.config["runner"] == "train":
        train(spec, devices, seeds, args.control_seeds)
    else:
        serve(spec, devices, seeds, args.control_seeds, args.seconds)


if __name__ == "__main__":
    main()
