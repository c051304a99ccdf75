"""Compile observatory: a per-program ledger of every XLA compile, with
recompile forensics that name the offending argument.

Reference role: the framework's performance contracts are compile-shaped —
exactly-two-program serving (SERVING.md), zero steady-state recompiles,
bucketed prefill — yet ``mx_jit_compile_seconds`` (PR 2) can only *count*
compiles. This module (Google-Wide-Profiling flavored: always-on once armed,
cheap enough to leave running) records one ledger entry per compile event:

- program/family name, cache key, lowering+compile wall time;
- ``compiled.cost_analysis()`` flops / bytes accessed;
- ``compiled.memory_analysis()`` argument/output/temp/generated bytes;
- how many pallas kernels (``tpu_custom_call``) the compiled program holds;
- the donation map and an HLO fingerprint (StableHLO text hash);
- per-argument signature (shape, dtype, weak_type | static repr).

On any compile after the first for a family it runs **forensics**: the new
signature is diffed against the previous entry and the root cause is
emitted as ``mx_jit_recompiles_total{program=,cause=}`` where cause is one
of ``shape | dtype | weak_type | static_arg | new_bucket`` (``unknown``
when the python-visible signature is identical — e.g. a sharding change),
plus a span annotation and a flight-recorder event. The zero-recompile
test gates can then assert *why*, not just *how many*.

Entry points. Raw ``jax.jit`` calls are confined to the registered
observatory entry points (:data:`OBSERVATORY_ENTRY_POINTS`, enforced by
lint FL012); every other program family goes through :func:`ledgered_jit`
(a drop-in ``jax.jit`` that wraps the result in :func:`instrument_jit`) so
new families can't silently bypass the ledger.

Off-path contract: disabled (the default) the instrument wrapper is one
module-global bool check + delegated call; the ndarray hook is the
module-global-None dead branch shared with `stages.py` / `injection.py`.
Armed by ``MXNET_TELEMETRY`` via `util._apply_env_config`.
"""
from __future__ import annotations

import hashlib
import re
import threading
import time

from .locks import tracked_lock

from . import registry, tracing

__all__ = ["enable", "disable", "is_enabled", "reset", "ledger",
           "ledger_report", "record_compile", "signature_of",
           "instrument_jit", "ledgered_jit", "diagnose",
           "OBSERVATORY_ENTRY_POINTS", "CAUSES"]

# Files allowed to call jax.jit( directly — every other in-tree call site
# must use ledgered_jit (or carry a justified `# noqa: FL012`). Mirrored
# by tools/framework_lint.py FL012; keep the two lists in sync.
OBSERVATORY_ENTRY_POINTS = (
    "ndarray/ndarray.py",      # eager op-call cache (_cached_jit hook)
    "gluon/block.py",          # _CachedGraph warmup (recorded below)
    "serve/engine.py",         # prefill/decode families (instrument_jit)
    "parallel/sharded.py",     # DataParallel step (instrument_jit)
    "telemetry/compiles.py",   # ledgered_jit itself
)

CAUSES = ("first", "shape", "dtype", "weak_type", "static_arg",
          "new_bucket", "unknown")

_ENABLED = False
_LOCK = tracked_lock("telemetry.compiles", kind="lock")
_LEDGER: dict = {}            # family -> [entry dict, ...] (append order)
_BUCKETS: dict = {}           # family -> set of seen bucket values
_MAX_PER_FAMILY = 64          # bounded forensic history per family


# --------------------------------------------------------------------------
# signatures + forensics
# --------------------------------------------------------------------------

def _is_arraylike(x):
    return hasattr(x, "shape") and hasattr(x, "dtype") \
        and not isinstance(x, type)


def _sig_one(x, depth=0):
    if _is_arraylike(x):
        weak = bool(getattr(x, "weak_type", False)
                    or getattr(getattr(x, "aval", None), "weak_type", False))
        return ("array", tuple(x.shape), str(x.dtype), weak)
    if isinstance(x, (tuple, list)) and depth < 3:
        return ("static", type(x).__name__,
                tuple(_sig_one(v, depth + 1) for v in x[:32]))
    if isinstance(x, (int, float, bool, complex, str, bytes, type(None))):
        return ("static", repr(x)[:80])
    # never repr arbitrary objects here — repr of a device array (or a
    # container of them) syncs and formats the data
    return ("static", f"<{type(x).__name__}>")


def signature_of(args, kwargs=None):
    """Per-argument signature tuple: arrays become
    ``('array', shape, dtype, weak_type)``, everything else
    ``('static', repr)``. Kwargs are appended sorted by name so the diff
    is positionally stable."""
    sig = [(str(i),) + _sig_one(a) for i, a in enumerate(args)]
    for k in sorted(kwargs or ()):
        sig.append((str(k),) + _sig_one(kwargs[k]))
    return tuple(sig)


def _diff_elem(old, new, name):
    """Diff two _sig_one values; (cause, detail) or None. Recurses into
    container signatures so a shape change nested in a params tuple is
    still classified `shape` and names the leaf."""
    if old == new:
        return None
    if old[0] == "array" and new[0] == "array":
        if old[1] != new[1]:
            return "shape", f"arg {name}: shape {old[1]} -> {new[1]}"
        if old[2] != new[2]:
            return "dtype", f"arg {name}: dtype {old[2]} -> {new[2]}"
        if old[3] != new[3]:
            return "weak_type", (f"arg {name}: weak_type "
                                 f"{old[3]} -> {new[3]}")
        return None
    if (old[0] == "static" and new[0] == "static"
            and len(old) == 3 and len(new) == 3
            and isinstance(old[2], tuple) and isinstance(new[2], tuple)
            and old[1] == new[1] and len(old[2]) == len(new[2])):
        for i, (o, n) in enumerate(zip(old[2], new[2])):
            hit = _diff_elem(o, n, f"{name}[{i}]")
            if hit is not None:
                return hit
        return None
    return "static_arg", f"arg {name}: {old[1:]} -> {new[1:]}"


def diagnose(prev_sig, new_sig, new_bucket=False):
    """Root-cause a recompile: diff two signatures and return
    ``(cause, detail)`` where detail names the offending argument.
    ``new_bucket`` short-circuits (a bucketed family growing a new bucket
    is expected growth, not shape churn)."""
    if new_bucket:
        return "new_bucket", "first compile for this bucket"
    if len(prev_sig) != len(new_sig):
        return "static_arg", (f"arity changed "
                              f"({len(prev_sig)} -> {len(new_sig)} args)")
    for old, new in zip(prev_sig, new_sig):
        hit = _diff_elem(tuple(old[1:]), tuple(new[1:]), new[0])
        if hit is not None:
            return hit
    return "unknown", "python-visible signature unchanged"


# --------------------------------------------------------------------------
# cost / memory analysis (compile-time only; never raises)
# --------------------------------------------------------------------------

def _abstractify(x):
    import jax

    if _is_arraylike(x):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    return x


def _parse_io_aliases(hlo_text):
    """Parse the compiled HLO's ``input_output_alias={ {0}: (1, {},
    may-alias), ... }`` header into ``[(output_index, param_number),
    ...]`` pairs — the donation map. An aliased pair means XLA updates
    that parameter's buffer IN PLACE for that output (the per-layer KV
    pool contract `serve/engine.py` relies on); a donated buffer
    MISSING here was copied instead. Brace-balanced scan, not a regex
    over the block: alias entries nest braces. Returns None when the
    header is absent (no aliasing)."""
    key = "input_output_alias={"
    start = hlo_text.find(key)
    if start < 0:
        return None
    i = start + len(key) - 1
    depth = 0
    end = None
    for j in range(i, min(len(hlo_text), i + 1_000_000)):
        c = hlo_text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                end = j
                break
    if end is None:
        return None
    block = hlo_text[i:end + 1]
    pairs = []
    for out_idx, param in re.findall(r"\{([\d,\s]*)\}:\s*\((\d+)", block):
        idx = tuple(int(t) for t in out_idx.split(",") if t.strip())
        pairs.append((idx[0] if len(idx) == 1 else idx, int(param)))
    return pairs or None


def _analyze(fn, args, kwargs):
    """Lower `fn` at the avals of `args` and pull XLA's own accounting:
    flops/bytes from cost_analysis, the four memory_analysis buckets, and
    a StableHLO fingerprint, and the number of pallas kernels
    (``tpu_custom_call``) left in the compiled program. Re-lowers once
    per COMPILE EVENT (telemetry on only); lowering from
    ShapeDtypeStructs never touches donated buffers. Returns (flops,
    bytes, memory, fingerprint, tpu_custom_calls) — all None on any
    failure (abstract lowering isn't possible for every program)."""
    try:
        sds = [_abstractify(a) for a in args]
        skw = {k: _abstractify(v) for k, v in (kwargs or {}).items()}
        lowered = fn.lower(*sds, **skw)
        text = lowered.as_text()
        fingerprint = hashlib.sha1(text.encode()).hexdigest()[:12]
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        cost = cost or {}
        flops = cost.get("flops")
        nbytes = cost.get("bytes accessed")
        mem = None
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument": getattr(ma, "argument_size_in_bytes", None),
                "output": getattr(ma, "output_size_in_bytes", None),
                "temp": getattr(ma, "temp_size_in_bytes", None),
                "generated": getattr(ma, "generated_code_size_in_bytes",
                                     None),
            }
        aliases = kernels = None
        try:
            hlo = compiled.as_text()
            kernels = len(re.findall(
                r'custom_call_target="tpu_custom_call"', hlo))
            aliases = _parse_io_aliases(hlo)
        except Exception:  # noqa: FL006 — as_text is optional introspection
            pass
        if mem is not None and aliases is not None:
            mem["aliased"] = aliases
            mem["aliased_params"] = sorted({p for _, p in aliases})
        return flops, nbytes, mem, fingerprint, kernels
    except Exception:
        return None, None, None, None, None


def _trace_clean():
    import jax

    try:
        return jax.core.trace_state_clean()
    except Exception:
        return True


# --------------------------------------------------------------------------
# the ledger
# --------------------------------------------------------------------------

def record_compile(family, seconds, args=(), kwargs=None, fn=None,
                   bucket=None, cache_key=None, donate=None, observe=True):
    """Append one ledger entry for a compile event of `family` and, past
    the first entry, run forensics against the previous one. Returns the
    entry dict (None when the observatory is disabled). Never raises."""
    if not _ENABLED:
        return None
    try:
        return _record(family, seconds, args, kwargs, fn, bucket,
                       cache_key, donate, observe)
    except Exception:
        return None


def _record(family, seconds, args, kwargs, fn, bucket, cache_key, donate,
            observe):
    family = str(family)
    sig = signature_of(args, kwargs)
    flops = nbytes = mem = fingerprint = kernels = None
    if fn is not None and hasattr(fn, "lower") and _trace_clean():
        flops, nbytes, mem, fingerprint, kernels = _analyze(
            fn, args, kwargs)
    with _LOCK:
        entries = _LEDGER.setdefault(family, [])
        seen_buckets = _BUCKETS.setdefault(family, set())
        prev = entries[-1] if entries else None
        if prev is None:
            cause, detail = "first", None
        else:
            fresh_bucket = bucket is not None and bucket not in seen_buckets
            cause, detail = diagnose(prev["signature"], sig,
                                     new_bucket=fresh_bucket)
        if bucket is not None:
            seen_buckets.add(bucket)
        entry = {
            "program": family,
            "seq": len(entries) + 1,
            "ts": time.time(),
            "seconds": float(seconds),
            "cache_key": None if cache_key is None else str(cache_key)[:120],
            "bucket": bucket,
            "signature": sig,
            "donate": donate,
            "flops": flops,
            "bytes_accessed": nbytes,
            "memory": mem,
            "hlo_fingerprint": fingerprint,
            "tpu_custom_calls": kernels,
            "cause": cause,
            "detail": detail,
        }
        entries.append(entry)
        if len(entries) > _MAX_PER_FAMILY:
            del entries[:len(entries) - _MAX_PER_FAMILY]
    if observe:
        registry.observe_compile(family, seconds)
    if cause != "first":
        registry.counter("mx_jit_recompiles_total",
                         "jit recompiles past the first per program "
                         "family, by forensic root cause").inc()
        registry.counter("mx_jit_recompiles_total",
                         "jit recompiles past the first per program "
                         "family, by forensic root cause",
                         labels={"program": family, "cause": cause}).inc()
        tracing.annotate(recompile=family, recompile_cause=cause)
        tracing.event("jit.recompile", program=family, cause=cause,
                      detail=detail, seq=entry["seq"])
    return entry


def ledger(family=None):
    """Copy of the ledger: ``{family: [entry, ...]}`` (or one family's
    entry list)."""
    with _LOCK:
        if family is not None:
            return list(_LEDGER.get(str(family), ()))
        return {f: list(es) for f, es in _LEDGER.items()}


def ledger_report():
    """Per-family rollup joining what the registry's raw counters can't:
    compile count, total compile seconds, last-known flops / bytes, peak
    live bytes (argument+output+temp), and the recompile-cause histogram."""
    with _LOCK:
        out = {}
        for fam, entries in _LEDGER.items():
            causes: dict = {}
            peak = None
            flops = nbytes = None
            for e in entries:
                if e["cause"] != "first":
                    causes[e["cause"]] = causes.get(e["cause"], 0) + 1
                if e["flops"] is not None:
                    flops = e["flops"]
                if e["bytes_accessed"] is not None:
                    nbytes = e["bytes_accessed"]
                mem = e["memory"]
                if mem:
                    tot = sum(v for v in (mem.get("argument"),
                                          mem.get("output"),
                                          mem.get("temp")) if v)
                    peak = tot if peak is None else max(peak, tot)
            out[fam] = {
                "compiles": len(entries),
                "seconds": sum(e["seconds"] for e in entries),
                "flops": flops,
                "bytes_accessed": nbytes,
                "peak_bytes": peak,
                "causes": causes,
                "buckets": sorted(_BUCKETS.get(fam, ()),
                                  key=lambda b: (str(type(b)), str(b))),
                "last_fingerprint": entries[-1]["hlo_fingerprint"]
                if entries else None,
            }
        return out


def _flight_probe():
    """Flight-recorder context: the rollup plus the tail of each family's
    history (an OOM/crash dump should name the programs on device)."""
    if not _LEDGER:
        return None
    report = ledger_report()
    with _LOCK:
        tail = {f: [{k: e[k] for k in ("seq", "seconds", "bucket", "cause",
                                       "detail", "hlo_fingerprint")}
                    for e in es[-5:]]
                for f, es in _LEDGER.items()}
    return {"report": report, "tail": tail}


# --------------------------------------------------------------------------
# instrumented jit wrappers
# --------------------------------------------------------------------------

class _LedgeredJit:
    """Transparent wrapper over a jitted callable: detects compile events
    via ``_cache_size()`` deltas and feeds the ledger. Attribute access
    (``lower``, ``_cache_size``, ...) passes through, so call sites that
    introspect the jitted object (`SlotDecoder.xla_program_count`,
    shardcheck) keep working."""

    __slots__ = ("_fn", "_family", "_bucket_fn", "_donate", "__weakref__")

    def __init__(self, fn, family, bucket_fn=None, donate=None):
        self._fn = fn
        self._family = str(family)
        self._bucket_fn = bucket_fn
        self._donate = donate

    def __call__(self, *args, **kwargs):
        if not _ENABLED:            # the dead branch
            return self._fn(*args, **kwargs)
        try:
            cs0 = self._fn._cache_size()
        except Exception:
            cs0 = None
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        if cs0 is not None:
            try:
                compiled = self._fn._cache_size() > cs0
            except Exception:
                compiled = False
            if compiled:
                # donated arrays keep their aval (shape/dtype readable
                # after the buffer is consumed) — signatures built
                # post-call are safe
                bucket = None
                if self._bucket_fn is not None:
                    try:
                        bucket = self._bucket_fn(args, kwargs)
                    except Exception:
                        bucket = None
                record_compile(self._family, time.perf_counter() - t0,
                               args=args, kwargs=kwargs, fn=self._fn,
                               bucket=bucket, donate=self._donate)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return f"<ledgered_jit {self._family} of {self._fn!r}>"


def instrument_jit(fn, family, bucket=None, donate=None):
    """Wrap an ALREADY-jitted callable for the observatory. `bucket` is an
    optional ``(args, kwargs) -> value`` probe for bucketed families
    (chunked prefill) so bucket growth is classified ``new_bucket`` rather
    than shape churn. Recorded compile wall time is the whole triggering
    call (trace+compile+first run — same convention as the warmup sites)."""
    return _LedgeredJit(fn, family, bucket_fn=bucket, donate=donate)


def ledgered_jit(fn, *, family, bucket=None, **jit_kwargs):
    """Drop-in ``jax.jit`` for program families outside the registered
    entry points: ``ledgered_jit(f, family="symbol.executor", donate_argnums=...)``
    compiles with jax.jit and returns the instrumented wrapper. This is
    the sanctioned escape from lint FL012."""
    import jax

    donate = jit_kwargs.get("donate_argnums")
    return instrument_jit(jax.jit(fn, **jit_kwargs), family,
                          bucket=bucket, donate=donate)


# --------------------------------------------------------------------------
# arming
# --------------------------------------------------------------------------

def _ndarray_compile_hook(name, key, call_vals, seconds, jitted):
    # the eager site already feeds mx_jit_compile_seconds itself
    record_compile(f"eager.{name}", seconds, args=call_vals, fn=jitted,
                   cache_key=key, observe=False)


def _arm_hooks(on):
    import sys

    nd_mod = sys.modules.get("incubator_mxnet_tpu.ndarray.ndarray")
    if nd_mod is not None:
        nd_mod._COMPILE_HOOK = _ndarray_compile_hook if on else None


def enable():
    """Arm the observatory (idempotent): install the ndarray compile hook
    and the flight-recorder context probe. Armed automatically with
    ``MXNET_TELEMETRY`` (see util._apply_env_config)."""
    global _ENABLED
    _ENABLED = True
    _arm_hooks(True)


def disable():
    global _ENABLED
    _ENABLED = False
    _arm_hooks(False)


def is_enabled():
    return _ENABLED


def reset():
    """Drop all ledger state (tests)."""
    with _LOCK:
        _LEDGER.clear()
        _BUCKETS.clear()


# crash dumps always carry the program history (probe returns None while
# the ledger is empty — the weakly-bound-source idiom)
tracing.register_flight_context("compile_ledger", _flight_probe)
