#!/usr/bin/env python
"""``mx_mla_decode`` alone, at ``pangu718b.think``'s shapes, timed on the chip.

``python tools/mla_decode_micro.py [--parent DIR]`` builds one pool, table
and query of the cell's widths (64 slots, 128 heads, rows of 512 + 64 stored
640 wide in bfloat16, 768 pages of 16 rows a slot) from a fixed seed, and
for three sets of slot lengths (the cell's mix of 512-11,000 rows, every
slot full, 1-600 rows) prints one JSON line: the grid steps of 512 rows the
call walks, each kernel's largest |difference| from the XLA expression on
the same device, and each kernel's microseconds a call by the host clock
over ``--calls`` calls after a call that warms it, in ``--reps`` loops that
alternate which kernel goes first; ``<side>_us_step_median`` is the median
call over its grid steps. ``--parent`` names another checkout whose
``incubator_mxnet_tpu/ops/paged_attention.py`` is timed beside this one's
(``parent``); without it only this tree's kernel (``change``) runs.

The call is device-bound (one program, no host work inside), so the host
clock over a loop reads the kernel. Off the chip the kernel is interpreted
at toy widths: a rehearsal of the script, no time worth reading.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as onp  # noqa: E402

from incubator_mxnet_tpu.ops import paged_attention  # noqa: E402

RANK, ROPE, PAGE_ROWS, BLOCK_PAGES = 512, 64, 16, 32
SCALE = 192 ** -0.5


def _parent(root):
    spec = importlib.util.spec_from_file_location(
        "incubator_mxnet_tpu.ops.paged_attention_parent",
        os.path.join(root, "incubator_mxnet_tpu/ops/paged_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--parent", help="another checkout to time beside")
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--calls", type=int, default=40)
    args = ap.parse_args(argv)
    cpu = jax.default_backend() == "cpu"
    # slots, heads, table pages a slot, pool pages
    S, H, P, NP = (2, 8, 40, 100) if cpu else (64, 128, 768, 18240)
    W = paged_attention.latent_store_width(RANK + ROPE)
    rng = onp.random.default_rng(1618033989)
    pool = jnp.asarray(rng.normal(size=(NP, PAGE_ROWS, W)).astype(onp.float32),
                       jnp.bfloat16).at[..., RANK + ROPE:].set(0)
    table = jnp.asarray(rng.integers(1, NP, (S, P)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, H, W)).astype(onp.float32),
                    jnp.bfloat16).at[..., RANK + ROPE:].set(0)
    rows = P * PAGE_ROWS
    cases = {"cell_mix": rng.integers(512, min(11000, rows), S),
             "all_long": onp.full(S, rows),
             "short": rng.integers(1, 600, S)}
    sides = {"change": paged_attention}
    if args.parent:
        sides = {"parent": _parent(args.parent), **sides}
    xla = jax.jit(lambda q, p, t, n: paged_attention._xla_mla_decode(
        q, p, t, n, RANK, SCALE))
    fns = {tag: jax.jit(lambda q, p, t, n, m=m: m._pallas_mla_decode(
        q, p, t, n, RANK, SCALE, cpu, block_pages=BLOCK_PAGES))
        for tag, m in sides.items()}
    for name, lens in cases.items():
        n = jnp.asarray(lens, jnp.int32)
        steps = int(sum(-(-int(ln) // (PAGE_ROWS * BLOCK_PAGES))
                        for ln in lens))
        ref = onp.asarray(xla(q, pool, table, n).astype(jnp.float32))
        res = {"grid_steps": steps, "rows": int(sum(lens)),
               "ref_scale": float(onp.abs(ref).max())}
        times = {tag: [] for tag in fns}
        for tag, f in fns.items():
            got = onp.asarray(f(q, pool, table, n).astype(jnp.float32))
            res[tag + "_max_abs_diff"] = float(onp.abs(got - ref).max())
        for rep in range(args.reps):
            order = list(fns) if rep % 2 == 0 else list(fns)[::-1]
            for tag in order:
                fns[tag](q, pool, table, n).block_until_ready()
                t = time.perf_counter()
                for _ in range(args.calls):
                    r = fns[tag](q, pool, table, n)
                r.block_until_ready()
                times[tag].append((time.perf_counter() - t) / args.calls
                                  * 1e6)
        for tag, v in times.items():
            v = sorted(v)
            res[tag + "_us_call"] = v
            res[tag + "_us_step_median"] = v[len(v) // 2] / max(steps, 1)
        print(name, json.dumps(res), flush=True)
    print(json.dumps({"device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main()
