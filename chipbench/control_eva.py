#!/usr/bin/env python3
"""chipbench/control_eva.py — `control.py` for a cell of `runners/serve_eva.py`.

Not part of a benchmark run. `control.py` drives `runners/serve.py` by its
module name; this script puts the cell's own runner in that place (it offers
the same `run`, `start`, `window`, `reference_logits`, `gaps`, `gap_checks`)
and then *is* `control.py`: the same arguments, the same lines.

    python3 chipbench/control_eva.py --workload evabyte.docs --seeds 1,2 \
        [--control-seeds 1] [--seconds 45]
    python3 chipbench/control_eva.py --workload evabyte.docs --sweep 0.2,0.25 \
        --seeds 1 --seconds 60

With `--variants` it instead serves `--seeds` once each and compares what the
window served with the reference computed with one part of the layer left
out, through the cell's own comparison at the cell's limits: the summaries
skipped (`R` empty), `adaptive_mu_k` dropped, `alpha` uniform (`adaptive_phi`
zero). A program that left the same part out would read the same gaps with
the sides exchanged; each has to come out not correct. The switch is here,
in the reference's place, never in the program.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chipbench.runners  # noqa: E402
from chipbench import control  # noqa: E402
from chipbench.lib import harness  # noqa: E402


def variants(ref):
    """name -> the reference's functions with one part left out."""
    import jax.numpy as jnp

    full_attention, full_summaries = ref.window_attention, ref.summaries

    def no_summaries(qry, k, v, k_hat, v_hat, t0, window, chunk, q=lambda x: x):
        # R empty: every chunk's window is pushed past every query's
        return full_attention(qry, k, v, k_hat[:0], v_hat[:0], t0, window,
                              chunk, q)

    def no_mu(k, v, phi, mu, chunk, q=lambda x: x):
        return full_summaries(k, v, phi, jnp.zeros_like(mu), chunk, q)

    def uniform_alpha(k, v, phi, mu, chunk, q=lambda x: x):
        return full_summaries(k, v, jnp.zeros_like(phi), mu, chunk, q)

    return {"skip_summaries": {"window_attention": no_summaries},
            "drop_mu": {"summaries": no_mu},
            "uniform_alpha": {"summaries": uniform_alpha}}


def run_variants(spec, devices, seeds, seconds):
    runner = chipbench.runners.serve
    ref = harness.module_of("reference", spec.config["family"], spec.root)
    limits = spec.cell["limits"]
    for seed in seeds:
        got = runner.run(control.quiet_env(spec, devices, seed, seconds))
        control.say(got["checks"], seed=seed, side="program")
        if not got["sample"]:
            continue
        for name, patch in variants(ref).items():
            kept = {k: getattr(ref, k) for k in patch}
            try:
                for k, fn in patch.items():
                    setattr(ref, k, fn)
                ref._programs.cache_clear()  # noqa: SLF001
                logits, served = runner.reference_logits(spec, seed,
                                                         got["sample"])
            finally:
                for k, fn in kept.items():
                    setattr(ref, k, fn)
                ref._programs.cache_clear()  # noqa: SLF001
            control.say(runner.gap_checks(runner.gaps(logits, served), limits),
                        seed=seed, side="variant_" + name,
                        tokens_differ=float(
                            (logits.argmax(-1) != served).mean()))


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variants", action="store_true")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--root", default=harness.CHIPBENCH)
    ap.add_argument("--any-device", action="store_true")
    args, _ = ap.parse_known_args(argv)       # the rest is `control.py`'s
    spec = harness.Spec(args.workload, args.root)
    chipbench.runners.serve = harness.module_of(
        "runners", spec.config["runner"], spec.root)
    if not args.variants:
        return control.main(argv)
    harness.configure_compile_cache()
    devices = harness.find_devices(spec.cell["chips"], not args.any_device)
    run_variants(spec, devices, [int(s) for s in args.seeds.split(",")],
                 args.seconds)


if __name__ == "__main__":
    main()
