#!/usr/bin/env python
"""The TPU compiler's own schedule of one pallas kernel, read without a chip.

``python tools/kernel_schedule.py mla_decode`` compiles the named kernel of
the main path at its cell's shapes (``paged_decode --shape
gpt2xl|evabyte|nemotron``: it serves three) for a DESCRIBED v5e (as
`tests/test_chip_compile.py` does) in a child process that asks libtpu to
dump its passes (``--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true`` in
``LIBTPU_INIT_ARGS``, set before jax loads the library), then reads the
kernel's last file, ``*-final_bundles.txt``: one VLIW bundle a line, in the
order the core walks them. Printed are the lines of the GRID LOOP (a grid
step walks the loop once), cut into stretches at the loop's start, at every
branch and branch target, and at the first and the last line of each kind
of work (``dma`` starts, ``dma.done.wait``, ``vmatmul``, ``vpop`` of the
MXU's results), each with its count of lines and what it holds; a last line
counts the loop's ``vmatmul``, ``vpop``, ``vxpose`` (an operand turned for
the MXU), ``vmatpush`` (an operand loaded into the MXU: ``.xpose`` turned
on its way in, or plain), ``vld``, ``vor``, ``dma``, waits and range checks
over all its bodies.

A stretch after a branch is walked only where the branch is not taken:
``or skip to 2363`` names the file line the branch goes to. A step's walk
is the lines outside every such region plus the regions it enters (of two
bodies written for the two parities of a step, one). ``delayed`` is a
branch target's place in the branches' own numbering, which counts the
delay slots the lines leave out: the difference of two is nearer to cycles.
No time comes out of this: the v5e's core runs at 1.5 GHz, so 1,500 delayed
bundles are 1 us if none waits, and the chip's trace says what a grid step
takes. What the dump shows before any chip time is spent is WHERE the lines
are: address arithmetic in a stretch of its own, or under the products.

libtpu takes a lock file: run this beside no other compile for a described
chip (the chip-compile tests skip while it runs).
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import subprocess
import sys
import tempfile

# the kernels that can be named (`mx_<name>` in a trace)
KERNELS = ("mla_decode", "paged_decode", "moe_experts", "ssm_decode")
# `paged_decode` serves three cells, each with a body of its own: slots,
# table pages, query heads, stored heads, head size, the pool's dtype
PAGED_SHAPES = {
    "gpt2xl": (8, 64, 25, 25, 64, "float32"),       # gpt2xl.chat (packed)
    "evabyte": (8, 248, 32, 32, 128, "bfloat16"),   # evabyte.docs
    "nemotron": (64, 192, 32, 2, 128, "bfloat16"),  # nemotron3super.turns
}

_BUNDLE = re.compile(
    r"^\s*(0x[0-9a-f]+|\d+)\s+(LH|LB|LE|PB|PF|CT)?:?\s*(>*)\s*\{(.*)$")
# an instruction opens its ``;;``-separated part of a bundle (a comment may
# quote another instruction: a range check names the copy it guards)
_OP = re.compile(r"^\s*%[\w.]+ = ([a-z][a-z0-9_.]*)")
_BRANCH = re.compile(r"= sbr\.rel .*?target bundleno = (\d+)")
# the kinds of work a stretch is cut at, and how an opcode is told
KINDS = collections.OrderedDict([
    ("dma", lambda op: op.startswith("dma.") and not op.startswith(
        "dma.done")),
    ("wait", lambda op: op.startswith("dma.done")),
    ("vmatmul", lambda op: op.startswith("vmatmul")),
    ("vpop", lambda op: op.startswith("vpop.f32.mrf")),
    # an operand turned on its way into the MXU: a product written the
    # wrong way round for the array pays one a tile
    ("vxpose", lambda op: op.startswith("vxpose")),
    # an operand loaded into the MXU, a pass of 16 bfloat16 rows each (8 a
    # 128 x 128 tile): the side of a product the array holds, turned on its
    # way in or not
    ("vmatpush.xpose", lambda op: op.startswith("vmatpush")
     and ".xpose" in op),
    ("vmatpush", lambda op: op.startswith("vmatpush") and ".xpose" not in op),
    # vector loads, and the ORs that join two half-loads of a bfloat16 vreg
    # from a pool tiled (8, 128)(2, 1)
    ("vld", lambda op: op == "vld"),
    ("vor", lambda op: op.startswith("vor.")),
    ("check", lambda op: op == "shalt.err"),
])
_CUT_KINDS = ("dma", "wait", "vmatmul", "vpop")


def parse(text):
    """The bundles of a ``final_bundles`` file: a list of dicts ``line``
    (1-based, in the file), ``bundle`` (the number the compiler gave it),
    ``mark`` (LH / LB / LE / PB / PF / CT or None), ``depth`` (loops it is
    inside), ``kinds`` (a Counter over `KINDS`) and ``target`` (the bundle
    number a branch in it goes to, or None)."""
    out = []
    for at, raw in enumerate(text.splitlines(), 1):
        m = _BUNDLE.match(raw)
        if m is None:
            continue
        number, mark, depth, body = m.groups()
        kinds = collections.Counter()
        for part in body.split(";;"):
            op = _OP.match(part)
            for kind, is_kind in KINDS.items():
                if op and is_kind(op.group(1)):
                    kinds[kind] += 1
        branch = _BRANCH.search(body)
        out.append({"line": at, "bundle": int(number, 0), "mark": mark,
                    "depth": len(depth), "kinds": kinds,
                    "target": int(branch.group(1)) if branch else None})
    return out


def branch_lines(bundles):
    """``{target: file line}`` for every branch target. A branch names its
    target in a numbering of its own (it counts the delay slots that the
    file's lines leave out), so a target is found by its rank: the distinct
    targets, in order, are the marked lines, in order. Empty where the two
    do not pair off."""
    marked = [b["line"] for b in bundles if b["mark"] is not None]
    targets = sorted({b["target"] for b in bundles
                      if b["target"] is not None})
    return dict(zip(targets, marked)) if len(marked) == len(targets) else {}


def grid_loop(bundles):
    """The bundles of the outermost loop, what one grid step walks: from
    the first to the last bundle marked as inside a loop (a branch's empty
    delay slots between them carry no mark)."""
    inside = [i for i, b in enumerate(bundles) if b["depth"] >= 1]
    return bundles[inside[0]:inside[-1] + 1] if inside else []


def stretches(loop, line_of=None):
    """Cut the loop's bundles (see the module docstring). Each stretch: a
    dict ``first`` / ``last`` (file lines), ``lines``, ``kinds`` (summed),
    ``skip_to`` (the file line a branch just before the stretch goes to,
    from `line_of`, `branch_lines`' dict: the stretch, and all up to that
    line, is walked only where the branch is not taken; "?" where the
    target's line is not known) and ``delayed`` (where the stretch starts
    at a branch target: the target's own number, which counts delay slots,
    so that the difference of two is nearer to cycles than their lines')."""
    if not loop:
        return []
    line_of = line_of or {}
    delayed = {line: target for target, line in line_of.items()}
    cuts = {0}
    for kind in _CUT_KINDS:
        has = [i for i, b in enumerate(loop) if b["kinds"][kind]]
        if has:
            cuts.update((has[0], has[-1] + 1))
    skip = {}
    for i, b in enumerate(loop):
        if b["mark"] is not None:
            cuts.add(i)
        if b["target"] is not None:
            cuts.add(i + 1)
            skip[i + 1] = line_of.get(b["target"], "?")
    cuts = sorted(c for c in cuts if c < len(loop)) + [len(loop)]
    out = []
    for a, z in zip(cuts, cuts[1:]):
        part = loop[a:z]
        out.append({
            "first": part[0]["line"], "last": part[-1]["line"],
            "lines": len(part),
            "kinds": sum((b["kinds"] for b in part), collections.Counter()),
            "skip_to": skip.get(a), "delayed": delayed.get(part[0]["line"])})
    return out


def report(name, text, out=sys.stdout):
    bundles = parse(text)
    loop = grid_loop(bundles)
    if not loop:
        print(f"{name}: {len(bundles)} bundles, no loop", file=out)
        return
    print(f"{name}: {len(bundles)} bundles, the grid loop {len(loop)} "
          f"(file lines {loop[0]['line']}-{loop[-1]['line']})", file=out)
    print(f"{'file lines':>11}  {'count':>5}  {'delayed':>7}  holds",
          file=out)
    for p in stretches(loop, branch_lines(bundles)):
        holds = ", ".join(f"{n} {k}" for k, n in p["kinds"].items() if n)
        if p["skip_to"] is not None:
            holds = f"[or skip to {p['skip_to']}] " + holds
        print(f"{p['first']:>5}-{p['last']:<5}  {p['lines']:>5}  "
              f"{p['delayed'] if p['delayed'] is not None else '':>7}  "
              f"{holds}", file=out)
    held = sum((b["kinds"] for b in loop), collections.Counter())
    print("in the loop, every body counted: " + ", ".join(
        f"{held[k]} {k}" for k in ("vmatmul", "vpop", "vxpose",
                                   "vmatpush.xpose", "vmatpush", "vld", "vor",
                                   "dma", "wait", "check")), file=out)


# ---------------------------------------------------------------------------
# the child: compile one kernel for a described v5e, dumping
# ---------------------------------------------------------------------------

def _compile(kernel, dump_dir, shape):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "")
        + f" --xla_jf_dump_to={dump_dir} --xla_jf_dump_llo_text=true")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from incubator_mxnet_tpu.ops import moe, paged_attention, ssm

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

    def arg(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    if kernel == "mla_decode":      # pangu718b.think: 64 slots x 768 pages
        def fn(q, pool, table, lengths):
            return paged_attention._pallas_mla_decode(
                q, pool, table, lengths, 512, 192 ** -0.5, False)
        args = (arg((64, 128, 640)), arg((18240, 16, 640)),
                arg((64, 768), i32), arg((64,), i32))
    elif kernel == "paged_decode":  # pages of 16 tokens, a full pool
        def fn(q, k, v, table, lengths):
            return paged_attention._pallas_paged_decode(
                q, k, v, table, lengths, False)
        S, P, hq, hk, d, dtype = PAGED_SHAPES[shape]
        pool = arg((S * P + 1, hk) + paged_attention.page_store_shape(16, d),
                   jnp.dtype(dtype))
        args = (arg((S, hq, d), jnp.dtype(dtype)), pool, pool,
                arg((S, P), i32), arg((S,), i32))
    elif kernel == "ssm_decode":    # nemotron3super.turns: 64 slots' state
        def fn(state, x, b, c, dt, a, d, active):
            return ssm._pallas_decode(state, x, b, c, dt, a, d, active, False)
        args = (arg((64,) + ssm.state_store_shape(128, 64, 128, 8), f32),
                arg((64, 128, 64), f32),
                arg((64, 8, 128), f32), arg((64, 8, 128), f32),
                arg((64, 128), f32), arg((128,), f32), arg((128,), f32),
                arg((64,), jnp.bool_))
    else:                           # pangu718b.think's decode step: 64 rows
        # `held_experts` asks the backend, which is the CPU here
        moe._dispatch.interpret_default = lambda: False

        def fn(u, ids, w, wg, wu, wd):
            return moe.held_experts(u, ids, w, (wg, wu, wd), (0, 16), None,
                                    step="decode", impl="pallas")
        args = (arg((64, 7680)), arg((64, 8), i32), arg((64, 8), f32),
                arg((16, 7680, 2048)), arg((16, 7680, 2048)),
                arg((16, 2048, 7680)))
    jax.jit(fn).lower(*args).compile()
    sys.stdout.flush()
    os._exit(0)     # libtpu may abort on a normal exit; the files are written


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kernel", choices=KERNELS)
    ap.add_argument("--shape", choices=sorted(PAGED_SHAPES), default="gpt2xl",
                    help="which cell's shape `paged_decode` is compiled at")
    ap.add_argument("--keep", metavar="DIR",
                    help="leave the compiler's dump in DIR (some 3,000 "
                         "files) instead of a temporary directory")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if a.child:
        _compile(a.kernel, a.keep, a.shape)
    with tempfile.TemporaryDirectory() as tmp:
        dump = a.keep or tmp
        os.makedirs(dump, exist_ok=True)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            p for p in (root, os.environ.get("PYTHONPATH")) if p))
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), a.kernel, "--child",
             "--keep", dump, "--shape", a.shape], env=env,
            capture_output=True, text=True)
        files = sorted(glob.glob(os.path.join(dump, "*-final_bundles.txt")))
        files = [f for f in files if "schedule-analysis" not in f
                 and f"-mx_{a.kernel}" in os.path.basename(f)]
        if not files:
            sys.stderr.write(done.stdout[-2000:] + done.stderr[-4000:])
            raise SystemExit(f"no final_bundles file of mx_{a.kernel} in "
                             f"{dump} (child exited {done.returncode})")
        for f in files:
            with open(f) as fh:
                report(os.path.basename(f).split("-", 1)[1]
                       .rsplit("-", 2)[0], fh.read())
    return 0


if __name__ == "__main__":
    sys.exit(main())
