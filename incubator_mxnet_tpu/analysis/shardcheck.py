"""Static sharding / partition-spec analyzer (`mx.analysis.shardcheck`).

GSPMD (Xu et al., 2021) validates and propagates shardings at compile
time; a wrong or missing PartitionSpec in THIS stack historically failed
only at pod runtime — as a silent full replication, a per-device OOM, or
an all-gather on the decode hot path. `shardcheck` is the pre-flight
analogue: it abstract-evaluates a program against a mesh (real, abstract,
or a plain ``{"axis": size}`` dict) and emits typed findings SC001-SC006
(`findings.SHARD_RULES`) before any chip is touched.

Three analysis tiers, each running when its inputs are available:

1. **spec tier** (always): pure host math over ``(aval, spec, mesh)``
   leaves — SC001 unconstrained large params, SC002 divisibility, SC003
   unknown axes, and the per-device byte estimate behind SC006.
2. **eval_shape tier** (needs ``fn``): output avals via `jax.eval_shape`
   + a jaxpr walk counting explicit collectives; donated-argument
   aliasing is resolved here (SC004) and output bytes enter the SC006
   estimate.
3. **simulated-mesh tier** (needs a real `jax.sharding.Mesh`, e.g. a CPU
   host forced to N devices via
   ``XLA_FLAGS=--xla_force_host_platform_device_count=N``): the program
   is lowered and compiled under the declared shardings and the HLO text
   is scanned for ``all-gather``/``all-reduce``/``reduce-scatter``/
   ``collective-permute``/``all-to-all`` with estimated bytes moved per
   step (SC005 flags full-operand re-materialization).

Env knobs (registered in `util._ENV_KNOBS`):
- ``MXNET_SHARDCHECK=warn|raise`` — trainers run shardcheck at
  construction and log/raise on findings (off by default).
- ``MXNET_SHARDCHECK_HBM_GB`` — per-device HBM budget for SC006.
"""
from __future__ import annotations

import logging
import math
import re

from .. import util
from ..base import MXNetError
from .findings import SHARD_RULES, ShardReport  # noqa: F401

__all__ = ["shardcheck", "SHARD_RULES", "ShardReport"]

_LOG = logging.getLogger("mxnet.analysis")

# Default SC001 threshold: replicating anything under 1 MiB is noise.
_REPLICATED_MIN_BYTES = 1 << 20

# HLO collective mnemonics scanned in the compiled text (tier 3) with the
# result-shape regex: `%x = f32[128,64]{1,0} all-gather(f32[64,64] ...)`.
_HLO_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                    "collective-permute", "all-to-all")
# the result type — one shape, or a tuple of them where XLA combined several
# operands into one collective — then the op
_HLO_RESULT_RE = re.compile(
    r"=\s+(\([^()]*\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"(" + "|".join(_HLO_COLLECTIVES) + r")(-start|-done)?\(")
_HLO_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                 "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                 "s32": 4, "u32": 4, "f32": 4,
                 "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

# jaxpr primitives that are explicit cross-shard transfers (shard_map /
# pmap-style code); GSPMD-inserted ones only appear in tier 3.
_JAXPR_COLLECTIVES = {"psum": "all-reduce", "psum2": "all-reduce",
                      # what `lax.psum` / `all_gather` trace to under a
                      # `shard_map` that checks varying axes (jax >= 0.7)
                      "psum_invariant": "all-reduce",
                      "all_gather_invariant": "all-gather",
                      "all_gather": "all-gather",
                      "reduce_scatter": "reduce-scatter",
                      "psum_scatter": "reduce-scatter",
                      "ppermute": "collective-permute",
                      "pgather": "all-gather", "all_to_all": "all-to-all"}


class _MeshView:
    """Uniform view over the accepted mesh forms: a real `Mesh` (enables
    the compile tier), an `AbstractMesh`, or a plain ``{"axis": size}``
    dict (spec-level analysis only)."""

    def __init__(self, mesh):
        import jax

        self.real = None
        if mesh is None:
            self.sizes = {}
        elif isinstance(mesh, dict):
            self.sizes = {str(k): int(v) for k, v in mesh.items()}
        else:
            self.sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
            if isinstance(mesh, jax.sharding.Mesh):
                self.real = mesh

    @property
    def n_devices(self):
        return math.prod(self.sizes.values()) if self.sizes else 1


def _is_spec_leaf(x):
    import jax

    return (x is None
            or isinstance(x, (jax.sharding.PartitionSpec,
                              jax.sharding.NamedSharding)))


def _as_spec(s):
    """NamedSharding -> its PartitionSpec; P()/None pass through."""
    import jax

    if isinstance(s, jax.sharding.NamedSharding):
        return s.spec
    return s


def _as_aval(leaf):
    """Any array-ish leaf -> ShapeDtypeStruct (NDArray unwrapped)."""
    import jax
    import numpy as onp

    if hasattr(leaf, "_data"):          # mx NDArray
        leaf = leaf._data
    if isinstance(leaf, jax.ShapeDtypeStruct):
        return leaf
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype)
    arr = onp.asarray(leaf)
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


def _nbytes(aval):
    try:
        item = aval.dtype.itemsize
    except Exception:
        item = 4
    return math.prod(aval.shape) * item if aval.shape else item


def _norm_entries(spec, rank):
    """Spec -> per-dim tuple-of-axis-names, padded with () to `rank`.
    None (unconstrained) and P() (explicitly replicated) both normalize
    to all-() — they differ only for SC001, handled by the caller."""
    entries = []
    for e in tuple(spec or ()):
        if e is None:
            entries.append(())
        elif isinstance(e, tuple):
            entries.append(tuple(e))
        else:
            entries.append((e,))
    while len(entries) < rank:
        entries.append(())
    return tuple(entries)


def _spec_leaves_for(arg, spec, where):
    """Broadcast one spec over an arg subtree, or zip a matching spec
    tree; returns one spec per array leaf of `arg`."""
    import jax

    n = len(jax.tree_util.tree_leaves(arg))
    if _is_spec_leaf(spec):
        return [spec] * n
    spec_leaves, spec_tree = jax.tree_util.tree_flatten(
        spec, is_leaf=_is_spec_leaf)
    arg_tree = jax.tree_util.tree_structure(arg)
    if spec_tree != arg_tree:
        raise ValueError(
            f"shardcheck: spec tree for {where} does not match the "
            f"argument structure ({spec_tree} vs {arg_tree})")
    return spec_leaves


def _flatten_with_specs(args, specs, name, prefix="arg"):
    """Yield (label, aval, spec, arg_index) per array leaf, broadcasting a
    single spec over an arg subtree or zipping a matching spec tree."""
    import jax

    if specs is None:
        specs = (None,) * len(args)
    if len(specs) != len(args):
        raise ValueError(
            f"shardcheck({name}): got {len(args)} abstract args but "
            f"{len(specs)} spec entries — pass one spec (or spec tree, or "
            f"None) per argument")
    out = []
    for i, (arg, spec) in enumerate(zip(args, specs)):
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        spec_leaves = _spec_leaves_for(arg, spec, f"{prefix} {i}")
        for (path, leaf), sp in zip(leaves, spec_leaves):
            label = f"{prefix}{i}{jax.tree_util.keystr(path)}"
            out.append((label, _as_aval(leaf), sp, i))
    return out


def _check_leaf(report, label, aval, spec, mv, replicated_min_bytes):
    """Spec-tier checks for one leaf; returns (per_device_bytes,
    shard_factor)."""
    nbytes = _nbytes(aval)
    rank = len(aval.shape)
    raw = _as_spec(spec)
    if raw is not None and len(tuple(raw)) > rank:
        report.add_rule(
            "SC002",
            f"{label}: spec {raw} has {len(tuple(raw))} entries but the "
            f"array has rank {rank}", severity="error", site=label,
            nbytes=nbytes)
        return nbytes, 1
    entries = _norm_entries(raw, rank)
    shard_factor = 1
    for dim, axes in enumerate(entries):
        factor = 1
        for ax in axes:
            if ax not in mv.sizes:
                report.add_rule(
                    "SC003",
                    f"{label}: spec names mesh axis {ax!r} but the mesh "
                    f"only has axes {tuple(mv.sizes) or '()'}",
                    severity="error", site=label, nbytes=nbytes)
                factor = None
                break
            factor *= mv.sizes[ax]
        if not factor or factor == 1:
            continue
        if aval.shape[dim] % factor:
            report.add_rule(
                "SC002",
                f"{label}: dim {dim} has size {aval.shape[dim]}, not "
                f"divisible by mesh axis {'x'.join(axes)} (size {factor}) "
                f"— jit rejects this sharding", severity="error",
                site=label, nbytes=nbytes)
        else:
            shard_factor *= factor
    if (raw is None and shard_factor == 1 and mv.n_devices > 1
            and nbytes >= replicated_min_bytes):
        report.add_rule(
            "SC001",
            f"{label}: no sharding constraint — {nbytes / 2**20:.1f} MiB "
            f"silently replicated on each of {mv.n_devices} devices",
            severity="warn", site=label, nbytes=nbytes)
    return -(-nbytes // shard_factor), shard_factor


def _scan_jaxpr(jaxpr, collectives):
    """Count explicit collective primitives (shard_map-style code) in a
    (closed) jaxpr, recursing into nested jaxprs."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        kind = _JAXPR_COLLECTIVES.get(eqn.primitive.name)
        if kind is not None:
            moved = sum(_nbytes(v.aval) for v in eqn.outvars
                        if hasattr(v, "aval"))
            rec = collectives.setdefault(kind, {"count": 0, "bytes": 0})
            rec["count"] += 1
            rec["bytes"] += moved
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                _scan_jaxpr(v, collectives)
            elif isinstance(v, (list, tuple)):
                for w in v:
                    if hasattr(w, "eqns") or hasattr(w, "jaxpr"):
                        _scan_jaxpr(w, collectives)


def _scan_hlo(hlo_text, collectives):
    """Collective census over compiled HLO: count + bytes of each result."""
    for m in _HLO_RESULT_RE.finditer(hlo_text):
        shapes = _HLO_SHAPE_RE.findall(m.group(1))
        if m.group(3) == "-start":      # (operand alias, result, ...)
            shapes = shapes[:1]
        rec = collectives.setdefault(m.group(2), {"count": 0, "bytes": 0})
        rec["count"] += 1
        for dtype, dims in shapes:
            rec["bytes"] += _HLO_ITEMSIZE.get(dtype, 4) * math.prod(
                int(d) for d in dims.split(",") if d.strip())


def _match_donations(report, leaves, out_leaves, donate_argnums):
    """Greedy shape/dtype aliasing of donated input leaves onto output
    leaves (XLA's own matching rule); emits SC004 on spec mismatch and
    returns (aliased_output_ids, donated_bytes)."""
    donate = set(donate_argnums or ())
    taken = set()
    donated_bytes = 0
    for label, aval, spec, argi in leaves:
        if argi not in donate:
            continue
        match = None
        for j, (olabel, oaval, ospec) in enumerate(out_leaves):
            if j in taken:
                continue
            if oaval.shape == aval.shape and oaval.dtype == aval.dtype:
                match = (j, olabel, oaval, ospec)
                break
        if match is None:
            continue
        j, olabel, oaval, ospec = match
        taken.add(j)
        donated_bytes += _nbytes(aval)
        in_e = _norm_entries(_as_spec(spec), len(aval.shape))
        out_e = _norm_entries(_as_spec(ospec), len(oaval.shape))
        if in_e != out_e:
            report.add_rule(
                "SC004",
                f"{label} is donated but sharded {_as_spec(spec)} while "
                f"its aliasing output {olabel} is {_as_spec(ospec)} — XLA "
                f"cannot alias the buffers; every step pays a silent "
                f"{_nbytes(aval) / 2**20:.1f} MiB copy",
                severity="warn", site=label, nbytes=_nbytes(aval))
    return taken, donated_bytes


def shardcheck(fn_or_step, *abstract_args, mesh=None, specs=None,
               out_specs=None, donate_argnums=(), hbm_budget_gb=None,
               hot_path=False, replicated_min_bytes=_REPLICATED_MIN_BYTES,
               name=None, mode=None, compile=True):
    """Pre-flight a program's sharding layout against a mesh.

    Parameters
    ----------
    fn_or_step : callable or None
        The jit-able step function. ``None`` restricts analysis to the
        spec tier (construction-time use, before batch shapes exist).
    *abstract_args
        One entry per fn argument: arrays, NDArrays, ShapeDtypeStructs,
        or pytrees thereof. Only shapes/dtypes are read.
    mesh : jax.sharding.Mesh | AbstractMesh | dict | None
        Real mesh enables the simulated-mesh compile tier; a
        ``{"axis": size}`` dict gives device-free spec analysis; None
        means single-device (specs naming axes raise SC003).
    specs / out_specs
        Per-argument (per-output-tree) PartitionSpec / NamedSharding /
        matching pytrees; ``None`` entries mean unconstrained.
    donate_argnums : tuple
        Mirrors `jax.jit` — drives SC004 and the SC006 donated-buffer
        accounting.
    hbm_budget_gb : float, optional
        Per-device budget for SC006; defaults to the
        ``MXNET_SHARDCHECK_HBM_GB`` env knob (unset = no budget check).
    hot_path : bool
        Mark the program as a latency hot path (serve decode): any
        sizeable all-gather is flagged SC005, not just full-operand ones.
    mode : "warn" | "raise" | None
        Escalation applied before returning (trainers pass the
        ``MXNET_SHARDCHECK`` knob value).
    compile : bool
        ``False`` skips the simulated-mesh compile tier even when a real
        mesh is available (construction-time / dryrun-stamp use, where a
        second full XLA compile of the step would be too expensive).

    Returns
    -------
    ShardReport
    """
    import jax

    fn = fn_or_step
    name = name or getattr(fn, "__name__", None) or "<specs>"
    mv = _MeshView(mesh)
    report = ShardReport(name, mesh_axes=mv.sizes)
    report.tiers.append("spec")

    leaves = _flatten_with_specs(abstract_args, specs, name)
    report.n_leaves = len(leaves)
    per_device = 0
    full_sharded_bytes = set()     # full sizes of leaves that ARE sharded
    for label, aval, spec, argi in leaves:
        pd, factor = _check_leaf(report, label, aval, spec, mv,
                                 replicated_min_bytes)
        per_device += pd
        if factor > 1:
            full_sharded_bytes.add(_nbytes(aval))

    spec_errors = [f for f in report.findings if f.severity == "error"]

    # ---- tier 2: eval_shape + jaxpr collective scan + donation aliasing
    out_leaves = []
    if fn is not None:
        avals = tuple(jax.tree.map(_as_aval, a) for a in abstract_args)
        try:
            out_shape = jax.eval_shape(fn, *avals)
            report.tiers.append("eval_shape")
        except Exception as e:  # analysis must never crash the caller
            report.note("trace-failed",
                        f"eval_shape failed ({type(e).__name__}: {e}); "
                        f"spec-tier results only", severity="info")
            out_shape = None
        if out_shape is not None:
            # tuple-output programs (the trainer step) pair each output
            # entry with its spec entry, so one None can cover a whole
            # aux subtree; otherwise a single spec broadcasts.
            if (isinstance(out_shape, (tuple, list))
                    and isinstance(out_specs, (tuple, list))
                    and not _is_spec_leaf(out_specs)
                    and len(out_specs) == len(out_shape)):
                out_leaves = [
                    (lbl, aval, sp) for lbl, aval, sp, _ in
                    _flatten_with_specs(tuple(out_shape), tuple(out_specs),
                                        name, prefix="out")]
            else:
                o_leaves = jax.tree_util.tree_flatten_with_path(
                    out_shape)[0]
                o_specs = _spec_leaves_for(out_shape, out_specs, "output")
                out_leaves = [
                    (f"out{jax.tree_util.keystr(p)}", _as_aval(l), sp)
                    for (p, l), sp in zip(o_leaves, o_specs)]
            aliased, donated = _match_donations(
                report, leaves, out_leaves, donate_argnums)
            report.donated_bytes = donated
            # non-aliased outputs are NEW per-device buffers
            for j, (olabel, oaval, ospec) in enumerate(out_leaves):
                if j in aliased:
                    continue
                entries = _norm_entries(_as_spec(ospec), len(oaval.shape))
                factor = 1
                for dim, axes in enumerate(entries):
                    f = math.prod(mv.sizes.get(a, 1) for a in axes)
                    if f > 1 and oaval.shape[dim] % f == 0:
                        factor *= f
                per_device += -(-_nbytes(oaval) // factor)
            try:
                _scan_jaxpr(jax.make_jaxpr(fn)(*avals), report.collectives)
                report.tiers.append("jaxpr")
            except Exception as e:
                report.note("jaxpr-scan-failed",
                            f"jaxpr collective scan skipped "
                            f"({type(e).__name__}: {e})", severity="info")

    # ---- tier 3: compile under the simulated mesh, scan HLO collectives
    if compile and fn is not None and mv.real is not None and not spec_errors:
        try:
            _compile_tier(report, fn, abstract_args, specs, out_specs,
                          donate_argnums, mv)
        except Exception as e:
            report.note("compile-failed",
                        f"simulated-mesh compile failed "
                        f"({type(e).__name__}: {e}); spec/eval_shape "
                        f"tiers only", severity="info")

    # SC005: collectives that re-materialize a full sharded operand, or —
    # on a declared hot path — any collective moving >= the SC001 floor.
    for op, rec in report.collectives.items():
        per_op = rec["bytes"] // max(rec["count"], 1)
        hits_full = (op in ("all-gather", "all-to-all")
                     and per_op in full_sharded_bytes)
        if hits_full or (hot_path and rec["bytes"] >= replicated_min_bytes):
            where = "decode/step hot path" if hot_path else "step"
            report.add_rule(
                "SC005",
                f"{op} x{rec['count']} moves ~{rec['bytes'] / 2**20:.2f} "
                f"MiB per {where}"
                + (" — re-materializes a full sharded operand on every "
                   "device" if hits_full else ""),
                severity="warn", nbytes=rec["bytes"])

    # ---- SC006: per-device HBM estimate vs budget
    report.per_device_bytes = int(per_device)
    if hbm_budget_gb is None:
        hbm_budget_gb = util.env_float("MXNET_SHARDCHECK_HBM_GB", 0.0)
    if hbm_budget_gb:
        report.budget_bytes = int(hbm_budget_gb * 2**30)
        if report.per_device_bytes > report.budget_bytes:
            report.add_rule(
                "SC006",
                f"per-device estimate {report.per_device_bytes / 2**20:.1f}"
                f" MiB exceeds the {hbm_budget_gb:g} GiB budget "
                f"(MXNET_SHARDCHECK_HBM_GB) — this job OOMs before the "
                f"first step completes", severity="error",
                nbytes=report.per_device_bytes)

    _count_findings(report)
    _apply_mode(report, mode)
    return report


def _compile_tier(report, fn, args, specs, out_specs, donate_argnums, mv):
    """Lower + compile under the real (simulated) mesh and census the HLO
    collectives; also records XLA's own per-device memory analysis."""
    import jax

    NS = jax.sharding.NamedSharding
    P = jax.sharding.PartitionSpec

    def to_sharding(sp):
        sp = _as_spec(sp)
        return NS(mv.real, sp if sp is not None else P())

    if specs is None:
        specs = (None,) * len(args)
    in_sh = []
    for i, (arg, spec) in enumerate(zip(args, specs)):
        treedef = jax.tree_util.tree_structure(arg)
        spec_leaves = _spec_leaves_for(arg, spec, f"arg {i}")
        in_sh.append(jax.tree_util.tree_unflatten(
            treedef, [to_sharding(s) for s in spec_leaves]))
    kw = {"in_shardings": tuple(in_sh)}
    if out_specs is not None:
        # leave None entries unspecified (compiler-chosen) — forcing
        # replication there would manufacture collectives that the real
        # program never runs
        kw["out_shardings"] = jax.tree.map(
            lambda s: None if s is None else to_sharding(s), out_specs,
            is_leaf=_is_spec_leaf)
    avals = tuple(jax.tree.map(_as_aval, a) for a in args)
    # the analyzer compiles programs ABOUT programs (simulated mesh);
    # deliberately outside the compile ledger
    jitted = jax.jit(fn, donate_argnums=tuple(donate_argnums or ()),
                     **kw)  # noqa: FL012
    compiled = jitted.lower(*avals).compile()
    _scan_hlo(compiled.as_text(), report.collectives)
    try:
        ma = compiled.memory_analysis()
        report.xla_memory = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
    except Exception as e:
        report.note("xla-memory-unavailable",
                    f"compiled.memory_analysis() unavailable on this "
                    f"backend ({type(e).__name__})", severity="info")
    report.tiers.append("compile")


def _count_findings(report):
    from ..telemetry import registry

    for f in report.findings:
        registry.counter("mx_shardcheck_findings_total",
                         "shardcheck findings by rule",
                         labels={"rule": f.kind}).inc()


def _apply_mode(report, mode):
    mode = (mode or "").strip().lower()
    if mode == "warn":
        for f in report.findings:
            _LOG.warning("MXNET_SHARDCHECK: %r", f)
    elif mode == "raise" and report.findings:
        raise MXNetError("MXNET_SHARDCHECK=raise\n" + report.summary())
