#!/usr/bin/env python
"""Sharding pre-flight CLI over the framework's real sharded programs.

Runs `mx.analysis.shardcheck` (rules SC001-SC006, see ANALYSIS.md) on a
SIMULATED mesh — the CPU host forced to N virtual devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — against:

1. the multichip-dryrun trainer: gluon BERT through
   `parallel.DataParallel` with Megatron TP param shardings on a dp x tp
   mesh (full tiers incl. the compiled-HLO collective census), and
2. the serve engine's two compiled program families (chunked prefill +
   decode) via `SlotDecoder.shardcheck_report()`.

Prints the findings table, the collective-cost table, and the per-device
byte summary; exits 1 if any program has findings.

Usage::

    python tools/shardcheck.py [--devices N] [--budget-gb F]
                               [--no-compile] [--dryrun]

``--dryrun`` emits only the one-line stamps (the same lines
`__graft_entry__.dryrun_multichip` prints into its metadata tail).
"""
import argparse
import os
import sys


def _force_virtual_devices(n):
    """Pin this process to a CPU host with n virtual devices BEFORE jax
    initializes."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}")
    import jax

    return jax


def _print_report(rep, verbose=True):
    print(rep.summary())
    if verbose and rep.tiers:
        print(f"  tiers: {'+'.join(rep.tiers)} | leaves: {rep.n_leaves}"
              + (f" | donated: {rep.donated_bytes / 2**20:.1f} MiB"
                 if rep.donated_bytes else ""))
    print()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual device count for the simulated mesh")
    ap.add_argument("--budget-gb", type=float, default=None,
                    help="per-device HBM budget for SC006 (overrides "
                         "MXNET_SHARDCHECK_HBM_GB)")
    ap.add_argument("--no-compile", action="store_true",
                    help="skip the simulated-mesh compile tier (fast; "
                         "spec + eval_shape analysis only)")
    ap.add_argument("--dryrun", action="store_true",
                    help="print only the one-line stamps")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    jax = _force_virtual_devices(args.devices)
    n = min(args.devices, len(jax.devices()))

    import numpy as onp

    from incubator_mxnet_tpu import gluon, np, optimizer
    from incubator_mxnet_tpu.models.bert import (bert_small,
                                                 tp_param_shardings)
    from incubator_mxnet_tpu.models.gpt import gpt_tiny
    from incubator_mxnet_tpu.parallel.mesh import make_mesh
    from incubator_mxnet_tpu.parallel.sharded import DataParallel
    from incubator_mxnet_tpu.serve.engine import SlotDecoder

    # same dp x tp factorization as the multichip dryrun
    tp = 1
    for cand in (4, 2):
        if n % cand == 0:
            tp = cand
            break
    dp = n // tp
    mesh = make_mesh({"dp": dp, "tp": tp}, devices=jax.devices()[:n])
    if not args.dryrun:
        print(f"simulated mesh: dp={dp} x tp={tp} over {n} virtual CPU "
              f"devices\n")

    reports = []

    # ---- 1. trainer: the dryrun gluon BERT under DataParallel ----
    net = bert_small(vocab_size=256, max_length=32, dropout=0.1,
                     seq_shard_axis="tp")
    net.initialize()
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(out, y):
        mlm_scores, _ = out
        return ce(mlm_scores.reshape(-1, 256), y.reshape(-1))

    dpar = DataParallel(net, mlm_loss, optimizer.Adam(learning_rate=1e-4),
                        mesh=mesh, param_shardings=tp_param_shardings(net))
    rng = onp.random.RandomState(0)
    batch = 2 * dp
    tokens = np.array(rng.randint(0, 256, (batch, 16)).astype("int32"))
    labels = np.array(rng.randint(0, 256, (batch, 16)).astype("int32"))
    rep = dpar.shardcheck_report(tokens, labels,
                                 hbm_budget_gb=args.budget_gb,
                                 compile=not args.no_compile)
    reports.append(rep)

    # ---- 2. serve: both compiled program families ----
    m = gpt_tiny(vocab_size=97, max_length=64, dropout=0.0)
    m.initialize()
    sd = SlotDecoder(m, max_slots=4, max_len=64)
    serve_reps = sd.shardcheck_report(hbm_budget_gb=args.budget_gb)
    reports.extend(serve_reps.values())

    if args.dryrun:
        for rep in reports:
            print(rep.stamp())
    else:
        for rep in reports:
            _print_report(rep)
        total = sum(len(r) for r in reports)
        print(f"{total} finding(s) across {len(reports)} program(s)")
    return 1 if any(len(r) for r in reports) else 0


if __name__ == "__main__":
    raise SystemExit(main())
