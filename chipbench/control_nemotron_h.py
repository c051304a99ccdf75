#!/usr/bin/env python3
"""chipbench/control_nemotron_h.py — `control.py` for a cell of
`runners/serve_nemotron_h.py`.

Not part of a benchmark run. As `control_pangu.py`: it puts the cell's own
runner in `runners/serve.py`'s place and then *is* `control.py` (readings over
seeds, the int8 control, `--sweep`):

    python3 chipbench/control_nemotron_h.py --workload nemotron3super.turns \
        --seeds 1,2 [--control-seeds 1] [--seconds 45]
    python3 chipbench/control_nemotron_h.py --workload nemotron3super.turns \
        --sweep 6,8,10 --seeds 1 --seconds 45

With `--variants` it instead serves `--seeds` once each and compares what the
window served with the reference computed with a fault planted, through the
cell's own comparison at the cell's limits:

* `state_not_reset`: every sampled request starts from the recurrent state
  and convolution tail that ANOTHER sampled request left at its end (what a
  slot holds when it changes hands and the first chunk does not zero it);
* `state_bf16`: the recurrent state rounded to bfloat16 after every token
  (what a state leaf kept in the weights' dtype would do);
* `no_score_bias`: the experts chosen by the scores alone;
* `scaling_factor_one`: `routed_scaling_factor` 1 for 5;
* `drop_shared_expert`: the shared expert dropped;
* `int8`: both inputs of every matmul rounded to int8, at the served
  positions.

Each variant is read twice: on the served tokens (their gaps below the
variant's best logit) and on the STATE (`state_gap_first`, `state_gap_max`:
the recurrent state the variant leaves after the tokens that two of the
engine's slots had consumed when the window closed, against the reference's;
the program's own slots read `program_states`; `--states-only` reads the
variants on the state alone). A program with the same fault would read the
same gaps with the sides exchanged; each has to come out not correct. The
switch is here, in the reference's place, never in the program. (Every side's line is followed by
its gaps over the rows above each margin, `by_margin`: what
``limits["decisive_margin"]`` is chosen from.)
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chipbench.runners  # noqa: E402
from chipbench import control  # noqa: E402
from chipbench.lib import harness  # noqa: E402

#: margins (biased router scores) at which a side's gaps are read
MARGINS = (0.0, 0.0005, 0.001, 0.002, 0.005, 0.01, 0.02)


def variants(ref):
    """name -> the reference's functions with one part altered."""
    import jax
    import jax.numpy as jnp

    weights = ref.route_weights

    def in_bf16(state):
        # not a pair of converts: the TPU's compiler is allowed excess
        # precision and takes a float32 -> bfloat16 -> float32 trip out
        return jax.lax.reduce_precision(state, exponent_bits=8,
                                        mantissa_bits=7)

    def unbiased(score, bias):  # noqa: ARG001
        return score

    def scale_one(chosen, scale):  # noqa: ARG001
        return weights(chosen, 1.0)

    def no_shared(p, u, q=lambda x: x):  # noqa: ARG001
        return jnp.zeros_like(u)

    return {"state_bf16": {"carry": in_bf16},
            "no_score_bias": {"biased": unbiased},
            "scaling_factor_one": {"route_weights": scale_one},
            "drop_shared_expert": {"shared_expert": no_shared}}


def by_margin(g, margin, **kw):
    """A side's gaps over the rows whose `route_margin` lies above each of
    `MARGINS`: ``[share of the rows, mean gap, widest gap]``."""
    control.say(by_margin={
        str(least): [float(at.mean()), float(g[at].mean()),
                     float(g[at].max())]
        for least in MARGINS for at in [margin > least] if at.any()}, **kw)


def stale_start(spec, seed, picked, ref):
    """`forward`'s `initial` of a sample whose every request inherits what
    the request before it in the sample left behind."""
    tokens, _, _ = chipbench.runners.serve.served_rows(
        picked, spec.traffic["check_pad"])
    lengths = [r.prompt.size + len(r.tokens) - 1 for r in picked]
    finals = {}
    ref.forward(spec.config, seed, tokens, lengths, finals=finals)
    n = len(picked)
    return {(li, b): finals[li, (b - 1) % n] for li, b in finals}


def run_variants(spec, devices, seeds, seconds, only=None,
                 states_only=False):
    """Serve each seed once; read the chosen `only` variants (None: all,
    in the order given) of the same sample — `states_only`: on the slots'
    states alone, which costs a third of the reference's time."""
    runner = chipbench.runners.serve
    ref = harness.module_of("reference", spec.config["family"], spec.root)
    limits = spec.cell["limits"]
    sides = dict(variants(ref), state_not_reset={}, int8={})
    if only is not None:
        sides = {k: sides[k] for k in only}
    traffic = spec.traffic
    for seed in seeds:
        got = runner.run(control.quiet_env(spec, devices, seed, seconds))
        control.say(got["checks"], seed=seed, side="program",
                    itl_p50_ms=control.percentile(got["window"]["itl_ms"], 50))
        if not got["sample"]:
            continue
        g, margin = got["rows"]
        by_margin(g, margin, seed=seed, side="program")
        tokens, rows, served = runner.served_rows(
            got["sample"], traffic["check_pad"])
        n_rows = traffic["check_requests"] * traffic["output"]["hi"]
        padded = rows + [(0, 0)] * (n_rows - len(rows))
        # the state's own comparison: what a program with the fault would
        # leave in a slot, against what the reference leaves
        held = [s for s, _ in got["states"]]
        wanted, finals = got["state_wanted"]
        if got["state_gaps"] is not None:
            control.say(side="program_states", seed=seed,
                        contexts=[int(s.size) for s in held],
                        gaps=[float(g) for g in got["state_gaps"].ravel()])
        for name, patch in sides.items():
            kept = {k: getattr(ref, k) for k in patch}
            try:
                for k, fn in patch.items():
                    setattr(ref, k, fn)
                ref._programs.cache_clear()  # noqa: SLF001
                initial = stale_start(spec, seed, got["sample"], ref) \
                    if name == "state_not_reset" and not states_only \
                    else None
                logits = None if states_only else ref.logits_at(
                    spec.config, seed, tokens, padded,
                    "int8" if name == "int8" else "float32",
                    initial=initial)[:len(rows)]
                state_checks = []
                if held:
                    n = len(held)
                    stale = {(li, b): finals[li, (b - 1) % n]
                             for li, b in finals} \
                        if name == "state_not_reset" else None
                    faulty, _ = runner.reference_states(
                        spec, seed, held,
                        "int8" if name == "int8" else "float32", stale)
                    fault_gaps = runner.state_gaps(faulty, wanted)
                    state_checks = runner.state_checks(fault_gaps, limits)
                    control.say(side="variant_" + name + "_states",
                                seed=seed,
                                gaps=[float(g) for g in fault_gaps.ravel()])
            finally:
                for k, fn in kept.items():
                    setattr(ref, k, fn)
                ref._programs.cache_clear()  # noqa: SLF001
            if states_only:
                control.say(state_checks, seed=seed, side="variant_" + name)
                continue
            gv = runner.gaps(logits, served)
            control.say(runner.gap_checks(gv, limits, margin) + state_checks,
                        seed=seed, side="variant_" + name,
                        tokens_differ=float(
                            (logits.argmax(-1) != served).mean()))
            by_margin(gv, margin, seed=seed, side="variant_" + name)


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variants", nargs="?", const="all", default=None)
    ap.add_argument("--states-only", action="store_true")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--root", default=harness.CHIPBENCH)
    ap.add_argument("--any-device", action="store_true")
    args, _ = ap.parse_known_args(argv)       # the rest is `control.py`'s
    spec = harness.Spec(args.workload, args.root)
    mine = harness.module_of("runners", spec.config["runner"], spec.root)
    was = chipbench.runners.serve           # imported by `mine`
    chipbench.runners.serve = mine
    try:        # whoever imports `runners.serve` after this finds its own
        if args.variants is None:
            return control.main(argv)
        harness.configure_compile_cache()
        devices = harness.find_devices(spec.cell["chips"],
                                       not args.any_device)
        only = None if args.variants == "all" else args.variants.split(",")
        run_variants(spec, devices, [int(s) for s in args.seeds.split(",")],
                     args.seconds, only, args.states_only)
    finally:
        chipbench.runners.serve = was


if __name__ == "__main__":
    main()
