#!/usr/bin/env python3
"""chipbench/control_pangu.py — `control.py` for a cell of `runners/serve_pangu.py`.

Not part of a benchmark run. As `control_eva.py`: it puts the cell's own
runner in `runners/serve.py`'s place and then *is* `control.py` (readings over
seeds, the int8 control, `--sweep`):

    python3 chipbench/control_pangu.py --workload pangu718b.think --seeds 1,2 \
        [--control-seeds 1] [--seconds 45]
    python3 chipbench/control_pangu.py --workload pangu718b.think \
        --sweep 1.6,2.0,2.4 --seeds 1 --seconds 90

With `--variants` it instead serves `--seeds` once each and compares what the
window served with the reference computed with one part of the layer left
out or altered, through the cell's own comparison at the cell's limits: the
`q_rope . k_rope` term of the scores dropped; `routed_scaling_factor` 1 for
2.5; the shared expert dropped; the two post-norms of the sandwich dropped;
one held expert's pairs dropped (what a layer that is not dropless does); and
(`int8`) both inputs of every matmul rounded to int8, at the served
positions. A program with the same fault would read the same gaps with the
sides exchanged; each has to come out not correct. The switch is here, in the
reference's place, never in the program.

With `--flips` it reads, on the same served sample, why the program's gap is
what it is: the reference is computed three times — as it is (float32);
`bf16_free`, both inputs of every matmul rounded to bfloat16 (what the
program's arithmetic does) and the experts chosen from its own scores; and
`bf16_forced`, the same rounding with the float32 pass's CHOICE of experts
replayed (weights from its own scores at those experts) — and says, through
the cell's comparison, by how much the token each rounded pass would serve
lies below the float32 best. If the choice of experts is what carries the
gap, `bf16_free` reads as the program does and `bf16_forced` orders below.
It also counts the served rows at which `bf16_free` holds another set of
held experts than float32 in some expert layer, splits the PROGRAM's own
gaps by that flag (`program_at_flip_rows`, `program_elsewhere`), and counts
the flips left above each of `MARGINS`. (Every side's line is followed by its
gaps over the rows above each margin, `by_margin`: what
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


def variants(ref):
    """name -> the reference's functions with one part left out."""
    import jax.numpy as jnp

    scores, weights, ids = ref.scores, ref.route_weights, ref.held_ids

    def no_rope(q_nope, k_nope, q_rope, k_rope, q=lambda x: x):
        return scores(q_nope, k_nope, jnp.zeros_like(q_rope), k_rope, q)

    def scale_one(chosen, scale):  # noqa: ARG001
        return weights(chosen, 1.0)

    def no_shared(p, u, q=lambda x: x):  # noqa: ARG001
        return jnp.zeros_like(u)

    def no_post_norm(x, g, eps):  # noqa: ARG001
        return x

    def one_expert_dropped(s):
        return ids(s)[:-1]

    return {"drop_rope_term": {"scores": no_rope},
            "scaling_factor_one": {"route_weights": scale_one},
            "drop_shared_expert": {"shared_expert": no_shared},
            "drop_post_norms": {"post_norm": no_post_norm},
            "drop_one_experts_pairs": {"held_ids": one_expert_dropped}}


def run_variants(spec, devices, seeds, seconds, only=None, flips=False):
    """Serve each seed once; read the chosen `only` variants (None: all,
    in the order given) and, with `flips`, `read_flips`, of the same
    sample."""
    runner = chipbench.runners.serve
    ref = harness.module_of("reference", spec.config["family"], spec.root)
    limits = spec.cell["limits"]
    sides = dict(variants(ref), int8={})
    if only is not None:
        sides = {k: sides[k] for k in only}
    for seed in seeds:
        got = runner.run(control.quiet_env(spec, devices, seed, seconds))
        control.say(got["checks"], seed=seed, side="program",
                    itl_p50_ms=control.percentile(got["window"]["itl_ms"], 50))
        if not got["sample"]:
            continue
        g, margin = got["rows"]
        by_margin(g, margin, seed=seed, side="program")
        for name, patch in sides.items():
            kept = {k: getattr(ref, k) for k in patch}
            try:
                for k, fn in patch.items():
                    setattr(ref, k, fn)
                ref._programs.cache_clear()  # noqa: SLF001
                logits, served = runner.reference_logits(
                    spec, seed, got["sample"],
                    "int8" if name == "int8" else "float32")
            finally:
                for k, fn in kept.items():
                    setattr(ref, k, fn)
                ref._programs.cache_clear()  # noqa: SLF001
            gv = runner.gaps(logits, served)
            control.say(runner.gap_checks(gv, limits, margin),
                        seed=seed, side="variant_" + name,
                        tokens_differ=float(
                            (logits.argmax(-1) != served).mean()))
            by_margin(gv, margin, seed=seed, side="variant_" + name)
        if flips:
            read_flips(spec, seed, got["sample"], margin, ref)


#: margins (router logits) at which `--flips` reads the program's gaps
MARGINS = (0.0, 0.01, 0.02, 0.05, 0.1, 0.2, 0.4)


def by_margin(g, margin, **kw):
    """A side's gaps over the rows whose `route_margin` lies above each of
    `MARGINS`: ``[share of the rows, mean gap, widest gap]`` (what
    ``limits["decisive_margin"]`` is chosen from)."""
    control.say(by_margin={
        str(least): [float(at.mean()), float(g[at].mean()),
                     float(g[at].max())]
        for least in MARGINS for at in [margin > least] if at.any()}, **kw)


class Routing:
    """In the place of the reference's `route_step`: the calls' choices of
    experts kept in the order made (`forward`: a layer at a time, a request
    at a time), or an earlier pass's replayed."""

    def __init__(self, ref, replay=None):
        self.ref, self.ids, self.replay = ref, [], replay
        self.inner = ref._programs  # noqa: SLF001

    def __enter__(self):
        self.ref._programs = self.programs  # noqa: SLF001
        return self

    def __exit__(self, *exc):
        self.ref._programs = self.inner  # noqa: SLF001

    def programs(self, s, dtype):
        import jax
        import jax.numpy as jnp
        import numpy as onp

        from chipbench.lib import lower

        pr, ref, q = dict(self.inner(s, dtype)), self.ref, lower.ROUND[dtype]
        free = pr["route_step"]

        @jax.jit
        def forced(p, u, ids):
            with jax.default_matmul_precision("highest"):
                score = jax.nn.sigmoid(u @ p["mlp.gate.weight"].T)
                chosen = jnp.take_along_axis(score, ids, axis=-1)
                return (ids, ref.route_weights(chosen, s.route_scale),
                        ref.shared_expert(p, u, q))

        def route_step(p, u):
            n = len(self.ids)
            out = free(p, u) if self.replay is None \
                else forced(p, u, jnp.asarray(self.replay[n]))
            self.ids.append(onp.asarray(out[0]))
            return out

        pr["route_step"] = route_step
        return pr


def held_sets(ids, held):
    """(T,) a bit an expert held that the token chose."""
    import numpy as onp

    local = ids.astype(onp.int64) - held[0]
    ok = (local >= 0) & (local < held[1])
    return onp.where(ok, 1 << onp.where(ok, local, 0), 0).sum(-1)


def read_flips(spec, seed, picked, margin, ref):
    """The `--flips` readings of one served sample (the module docstring)."""
    import jax.numpy as jnp
    import numpy as onp

    from chipbench.lib import lower

    runner = chipbench.runners.serve
    limits = spec.cell["limits"]
    lower.ROUND.setdefault(
        "bfloat16", lambda x: x.astype(jnp.bfloat16).astype(jnp.float32))
    held = ref.sizes(spec.config).held
    _, rows, served = runner.base.served_rows(picked,
                                              spec.traffic["check_pad"])
    with Routing(ref) as exact:
        f32, _ = runner.reference_logits(spec, seed, picked, "float32")
    with Routing(ref) as free:
        lo_free, _ = runner.reference_logits(spec, seed, picked, "bfloat16")
    with Routing(ref, replay=exact.ids):
        lo_forced, _ = runner.reference_logits(spec, seed, picked, "bfloat16")
    # calls: an expert layer at a time, a request at a time
    n_b = len(picked)
    b, t = onp.asarray(rows).T
    flip = onp.zeros((len(exact.ids) // n_b, len(rows)), bool)
    for li in range(flip.shape[0]):
        for bb in range(n_b):
            a, c = exact.ids[li * n_b + bb], free.ids[li * n_b + bb]
            at = t[b == bb]
            flip[li, b == bb] = \
                held_sets(a[at], held) != held_sets(c[at], held)
    best = f32.argmax(-1)
    for side, logits in (("bf16_free", lo_free),
                         ("bf16_forced", lo_forced)):
        would = logits.argmax(-1)
        control.say(runner.gap_checks(runner.gaps(f32, would), limits,
                                      margin),
                    seed=seed, side=side,
                    tokens_differ=float((would != best).mean()))
    control.say(seed=seed, side="bf16_free_choice",
                pairs_of_row_and_layer_flipped=float(flip.mean()),
                rows_flipped=float(flip.any(0).mean()))
    g = runner.gaps(f32, served)
    # the rows above each margin at which `bf16_free` flipped all the same
    control.say(seed=seed, side="bf16_free_choice", flips_above_margin={
        str(least): int(flip.any(0)[margin > least].sum())
        for least in MARGINS})
    for side, at in (("program_at_flip_rows", flip.any(0)),
                     ("program_elsewhere", ~flip.any(0))):
        mine = g[at] if at.any() else onp.zeros(1)
        control.say(seed=seed, side=side, rows=int(at.sum()),
                    logit_gap_max=float(mine.max()),
                    logit_gap_mean=float(mine.mean()),
                    logit_gap_sum_share=float(
                        mine.sum() / max(g.sum(), 1e-30)),
                    tokens_differ=float(
                        (served != best)[at].mean()) if at.any() else 0.0)


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variants", nargs="?", const="all", default=None)
    ap.add_argument("--flips", action="store_true")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--root", default=harness.CHIPBENCH)
    ap.add_argument("--any-device", action="store_true")
    args, _ = ap.parse_known_args(argv)       # the rest is `control.py`'s
    spec = harness.Spec(args.workload, args.root)
    chipbench.runners.serve = harness.module_of(
        "runners", spec.config["runner"], spec.root)
    if args.variants is None and not args.flips:
        return control.main(argv)
    harness.configure_compile_cache()
    devices = harness.find_devices(spec.cell["chips"], not args.any_device)
    only = [] if args.variants is None else \
        None if args.variants == "all" else args.variants.split(",")
    run_variants(spec, devices, [int(s) for s in args.seeds.split(",")],
                 args.seconds, only, args.flips)


if __name__ == "__main__":
    main()
