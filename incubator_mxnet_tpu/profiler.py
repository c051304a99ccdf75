"""Profiler (reference: `python/mxnet/profiler.py` + `src/profiler/` — chrome
tracing JSON, per-op aggregate stats, true per-op DEVICE cost
`src/profiler/profiler.h:263`).

TPU-native: two complementary sources, merged at `dump()`:

- host funnel timing: `record_op` times each apply_op dispatch (imperative
  op latency — on an async device this measures dispatch, not execution);
- DEVICE timeline: `start()` begins a jax/XLA profiler trace (XPlane);
  `stop()` ends it and parses the captured chrome-trace, pulling the
  per-op device events (fusions, custom calls, pjit programs) and their
  durations. `dump()` writes ONE chrome://tracing JSON containing both
  lanes; `dumps()` appends a device-side aggregate table.

`set_config(profile_device=False)` disables the device trace;
`set_config(tensorboard_logdir=...)` additionally keeps the raw XPlane
artifacts where TensorBoard/XProf can load them.
"""
from __future__ import annotations

import atexit
import glob
import gzip
import json
import os
import shutil
import tempfile
import threading
import time
from collections import defaultdict

__all__ = ["set_config", "set_state", "start", "stop", "dump", "dumps",
           "pause", "resume", "Scope", "profiler_scope", "device_events",
           "event_stat_bytes", "event_stat_flops",
           "memory_stats", "live_buffer_table", "memory_snapshot",
           "analyze_memory"]

_CONFIG = {"filename": "profile.json", "profile_all": False,
           "profile_imperative": True, "aggregate_stats": True,
           "profile_device": True, "profile_memory": False}
_STATE = {"running": False, "jax_tracing": False, "trace_dir": None,
          "own_trace_dir": False}
_EVENTS: list = []
_DEVICE_EVENTS: list = []
_DEVICE_AGG = defaultdict(lambda: [0, 0.0])        # count, total_us
_AGG = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])  # count, total, min, max
_LOCK = threading.Lock()


_REMOTE_PENDING: list = []   # ('set_config', {...}) / ('set_state', 'run')


def set_config(**kwargs):
    """`profile_process='server'` queues the config as a REMOTE command:
    it ships to every process of the dist job at the next kvstore sync
    point and applies there (reference: `KVStoreServerProfilerCommand`
    kSetConfig riding ps-lite, `include/mxnet/kvstore.h:48` — the TPU
    build has no separate server processes, so 'server' means 'all
    processes of the job')."""
    if kwargs.pop("profile_process", "worker") == "server":
        _REMOTE_PENDING.append(("set_config", dict(kwargs)))
        if not _dist_active():      # degenerate job: we ARE the server
            _CONFIG.update(kwargs)
        return
    _CONFIG.update(kwargs)


def set_state(state="stop", profile_process="worker"):
    if profile_process == "server":
        _REMOTE_PENDING.append(("set_state", state))
        if _dist_active():
            return
    if state in ("run", "start"):
        start()
    else:
        stop()


def _dist_active():
    try:
        from .parallel import dist

        return dist.is_initialized() and dist.num_processes() > 1
    except Exception:
        return False


def sync_remote_commands():
    """Collective exchange+apply of queued 'server' profiler commands —
    called from KVStoreDist sync points (every process must participate;
    commands from ANY rank apply on ALL ranks)."""
    global _REMOTE_PENDING
    if not _dist_active():
        _REMOTE_PENDING = []
        return
    from .parallel import dist

    mine, _REMOTE_PENDING = _REMOTE_PENDING, []
    for cmds in dist.exchange_objs(mine):
        for kind, arg in cmds or []:
            if kind == "set_config":
                _CONFIG.update(arg)
            elif kind == "set_state":
                set_state(arg)


def start(profile_process="worker"):  # noqa: ARG001
    _STATE["running"] = True
    if not _CONFIG.get("profile_device", True):
        return
    # each start/stop cycle REPLACES the device timeline (a per-epoch
    # start/stop loop would otherwise grow the event list without bound)
    with _LOCK:
        _DEVICE_EVENTS.clear()
        _DEVICE_AGG.clear()
    del _PAUSED_INTERVALS[:]
    logdir = _CONFIG.get("tensorboard_logdir")
    if logdir:
        _STATE["trace_dir"] = logdir
        _STATE["own_trace_dir"] = False
    else:
        _STATE["trace_dir"] = tempfile.mkdtemp(prefix="mxtpu_prof_")
        _STATE["own_trace_dir"] = True
    import jax

    try:
        # wall-clock anchor: XPlane event timestamps are relative to the
        # MOMENT start_trace is called (session setup time included), so
        # the anchor must be captured BEFORE the call — capturing it
        # after used to shear the device lanes by the multi-second
        # profiler-session init on some backends. Measured on a v5e: the
        # rebased lanes sit 0.1-0.2 ms early (TELEMETRY.md). Spans that
        # must line up with the device to better than that are written
        # into the profiler's own trace (telemetry/tracing.py)
        _STATE["trace_t0_us"] = time.time() * 1e6
        jax.profiler.start_trace(_STATE["trace_dir"])
        _STATE["jax_tracing"] = True
    except Exception:
        _STATE["jax_tracing"] = False
        if _STATE.get("own_trace_dir") and _STATE.get("trace_dir"):
            shutil.rmtree(_STATE["trace_dir"], ignore_errors=True)
        _STATE["trace_dir"] = None


def stop(profile_process="worker"):  # noqa: ARG001
    _STATE["running"] = False
    if _STATE.get("jax_tracing"):
        import jax

        try:
            jax.profiler.stop_trace()
            _ingest_device_trace(_STATE["trace_dir"])
        except Exception as e:
            from .fault.retry import suppressed

            suppressed("profiler.stop_trace", e)   # device trace lost
        finally:
            if _STATE.get("own_trace_dir") and _STATE.get("trace_dir"):
                shutil.rmtree(_STATE["trace_dir"], ignore_errors=True)
            _STATE["trace_dir"] = None
        _STATE["jax_tracing"] = False


def _ingest_device_trace(trace_dir):
    """Parse the captured XPlane chrome-trace: keep the device/runtime
    lanes' complete events (+ their metadata rows, remapped clear of the
    host-funnel pid 0) and accumulate per-op device aggregates."""
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not paths:
        return
    with gzip.open(paths[-1]) as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    lanes = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            lanes[e["pid"]] = e.get("args", {}).get("name", "")
    t0 = _STATE.get("trace_t0_us", 0.0)
    with _LOCK:
        for e in events:
            pid = e.get("pid")
            if pid not in lanes:
                continue
            kept = dict(e)
            kept["pid"] = 1000 + pid       # host funnel events own pid 0
            if "ts" in kept:
                # rebase trace-relative µs onto the host epoch clock so
                # host dispatch and device execution correlate in one view
                kept["ts"] = float(kept["ts"]) + t0
                # honor pause()/resume(): the device trace records through
                # a pause, so filter its events out at ingest (metadata
                # rows carry no timestamp and always survive)
                if e.get("ph") != "M" and _in_paused_interval(kept["ts"]):
                    continue
            if e.get("ph") == "X":
                # normalize the per-version XPlane stat spellings into
                # canonical arg keys so every downstream consumer
                # (roofline, kernel census) reads one name
                b, fl = event_stat_bytes(kept), event_stat_flops(kept)
                if b is not None or fl is not None:
                    args = dict(kept.get("args") or {})
                    if b is not None:
                        args["bytes_accessed"] = b
                    if fl is not None:
                        args["flops"] = fl
                    kept["args"] = args
            _DEVICE_EVENTS.append(kept)
            if e.get("ph") == "X" and lanes[pid].startswith("/device:"):
                agg = _DEVICE_AGG[e.get("name", "?")]
                agg[0] += 1
                agg[1] += float(e.get("dur", 0))


def event_stat_bytes(e):
    """Bytes accessed by one trace event, from its XPlane stat args, or
    None when the trace carries no byte accounting for it. THE extraction
    path: `telemetry.roofline` and `telemetry.kernels` both route through
    here, so a new jax/XLA stat spelling (``bytes accessed`` vs
    ``bytes_accessed`` vs bare ``bytes``) is fixed in one place."""
    args = e.get("args") or {}
    for k, v in args.items():
        lk = k.lower()
        if "bytes" in lk and ("access" in lk or lk == "bytes"):
            try:
                return int(float(v))
            except (TypeError, ValueError):
                continue
    return None


def event_stat_flops(e):
    """FLOPs of one trace event from its XPlane stat args (``flops`` /
    ``model_flops`` / ``device_flops`` spellings), or None."""
    args = e.get("args") or {}
    for k, v in args.items():
        lk = k.lower().replace(" ", "_")
        if lk in ("flops", "model_flops", "device_flops",
                  "estimated_flops"):
            try:
                return int(float(v))
            except (TypeError, ValueError):
                continue
    return None


def device_events():
    """Parsed device-timeline events from the last stop() (list of chrome
    trace events; empty before any device trace completes). Events whose
    XPlane stats carry byte/FLOP accounting additionally expose the
    canonical ``bytes_accessed``/``flops`` arg keys (normalized at
    ingest), so consumers need not know the per-version stat spellings."""
    with _LOCK:
        return list(_DEVICE_EVENTS)


def device_op_totals():
    """{op name: (count, total_us)} aggregated from the /device: lanes
    only — true on-chip execution time, no host/launch events (what the
    aggregate table in dumps() prints)."""
    with _LOCK:
        return {k: (v[0], v[1]) for k, v in _DEVICE_AGG.items()}


_PAUSED_INTERVALS: list = []   # [start_us, end_us|None] epoch-µs, host clock


def pause(profile_process="worker"):  # noqa: ARG001
    """Stop host-side op recording AND mark the paused interval so device
    events are suppressed too.

    Scope: the host flag takes effect immediately (`record_op` checks it
    per op). The jax/XLA DEVICE trace cannot be paused mid-flight — it
    keeps recording until `stop()` — so instead the paused window
    [pause(), resume()] is remembered and `_ingest_device_trace` drops
    device events whose (rebased) timestamp falls inside it. Metadata
    rows (process/thread names) are always kept."""
    _STATE["running"] = False
    _PAUSED_INTERVALS.append([time.time() * 1e6, None])


def resume(profile_process="worker"):  # noqa: ARG001
    """Resume host-side op recording and close the paused interval (see
    `pause` for the device-trace suppression semantics)."""
    _STATE["running"] = True
    if _PAUSED_INTERVALS and _PAUSED_INTERVALS[-1][1] is None:
        _PAUSED_INTERVALS[-1][1] = time.time() * 1e6


def _in_paused_interval(ts_us):
    for start, end in _PAUSED_INTERVALS:
        if ts_us >= start and (end is None or ts_us <= end):
            return True
    return False


def is_running():
    return _STATE["running"]


def record_op(name, dur_s):
    """Called from the op funnel when profiling is active."""
    mem = None
    if _CONFIG.get("profile_memory"):
        mem = _live_bytes()
    with _LOCK:
        _EVENTS.append({"name": name, "ph": "X", "pid": 0, "tid": 0,
                        "ts": time.time() * 1e6, "dur": dur_s * 1e6})
        agg = _AGG[name]
        agg[0] += 1
        agg[1] += dur_s
        agg[2] = min(agg[2], dur_s)
        agg[3] = max(agg[3], dur_s)
        if mem is not None:
            m = _MEM_AGG[name]
            m[0] = max(m[0], mem)
            if mem > _MEM_STATE["peak"]:
                _MEM_STATE["peak"] = mem
                _MEM_STATE["peak_op"] = name


# ---------------------------------------------------------------------------
# memory profiler (reference: `src/profiler/storage_profiler.h:130`
# GpuDeviceStorageProfiler per-alloc attribution + kMemory profile mode,
# `src/profiler/profiler.h:265`)
# ---------------------------------------------------------------------------

_MEM_AGG = defaultdict(lambda: [0])                 # peak live bytes at op
_MEM_STATE = {"peak": 0, "peak_op": None}


def _live_bytes():
    import jax

    total = 0
    for a in jax.live_arrays():
        try:
            total += a.nbytes
        except Exception:  # noqa: FL006 — deleted/donated buffer racing the sweep
            pass
    return total


def memory_stats(device=None):
    """Per-device memory statistics. On TPU/GPU this surfaces the PJRT
    allocator's `bytes_in_use` / `peak_bytes_in_use`; on backends without
    allocator stats (CPU) it falls back to summed live-buffer bytes. The
    reference's `GpuDeviceStorageProfiler` csv role."""
    import jax

    devices = [device] if device is not None else jax.devices()
    out = {}
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            live = sum(a.nbytes for a in jax.live_arrays()
                       if d in getattr(a, "devices", lambda: set())())
            stats = {"bytes_in_use": live, "peak_bytes_in_use": live,
                     "source": "live_arrays"}
        out[str(d)] = dict(stats)
    return out


def live_buffer_table(top=20):
    """The largest live device buffers (shape, dtype, bytes) — per-alloc
    attribution in the spirit of the reference's storage profiler dump."""
    import jax

    rows = []
    for a in jax.live_arrays():
        try:
            rows.append((tuple(a.shape), str(a.dtype), int(a.nbytes)))
        except Exception:  # noqa: FL006 — deleted/donated buffer racing the sweep
            continue
    rows.sort(key=lambda r: -r[2])
    return rows[:top]


def memory_snapshot(path="memory.prof"):
    """Write a pprof-format device memory profile
    (`jax.profiler.device_memory_profile`) — loadable with `pprof` /
    TensorBoard memory viewer. Returns the path."""
    import jax

    with open(path, "wb") as f:
        f.write(jax.profiler.device_memory_profile())
    return path


def analyze_memory(fn, *args, static_argnums=None):
    """Compile `fn(*args)` and return XLA's memory analysis — argument /
    output / TEMP (activation) / alias bytes and the generated code size.
    The temp size is the compiler's actual activation-buffer plan, so it
    directly exposes what remat saves (used by `tests/test_profiler.py`
    to pin remat peak < no-remat peak). Works on every backend —
    compile-time analysis, nothing is executed."""
    import jax

    # AOT memory estimator: lower+compile for analysis only, nothing runs
    jitted = jax.jit(fn, static_argnums=static_argnums or ())  # noqa: FL012
    compiled = jitted.lower(*args).compile()
    an = compiled.memory_analysis()
    if an is None:                 # pragma: no cover - backend-dependent
        return None
    return {
        "argument_size_in_bytes": an.argument_size_in_bytes,
        "output_size_in_bytes": an.output_size_in_bytes,
        "temp_size_in_bytes": an.temp_size_in_bytes,
        "alias_size_in_bytes": an.alias_size_in_bytes,
        "generated_code_size_in_bytes": an.generated_code_size_in_bytes,
    }


def dump(finished=True, profile_process="worker"):  # noqa: ARG001
    """Write ONE chrome://tracing JSON holding the host dispatch lane
    (pid 0), the device/runtime lanes from the jax trace (reference:
    profiler.py:125 writes the C++ profiler's chrome trace), and — when
    span tracing is armed — the request/step span lanes from
    `telemetry.tracing` (all three in epoch µs; the device lanes through
    the anchor taken in `start`, 0.1-0.2 ms off on a v5e, TELEMETRY.md)."""
    path = _CONFIG["filename"]
    with _LOCK:
        merged = [{"name": "process_name", "ph": "M", "pid": 0,
                   "args": {"name": "host: op dispatch"}}]
        merged += list(_EVENTS)
        merged += list(_DEVICE_EVENTS)
    from .telemetry import tracing

    if tracing.is_enabled():
        merged += tracing.chrome_events()
    payload = {"traceEvents": merged, "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


def dumps(reset=False, format="table", sort_by="total", ascending=False,
          memory=False):
    """Aggregate per-op stats (reference: profiler.py:154): host dispatch
    table, then the device-timeline table when a trace was captured;
    `memory=True` appends the memory section (per-device allocator stats,
    observed live-bytes peak + the op at peak when
    `set_config(profile_memory=True)` sampled during the run, and the
    largest live buffers — the reference's kMemory mode +
    storage-profiler table). `format="json"` returns the same aggregates
    as a JSON string (host/device rows + optional memory section) instead
    of the text tables; `"table"` is the default text path."""
    if format not in ("table", "json"):
        raise ValueError(f"format must be 'table' or 'json', got {format!r}")
    with _LOCK:
        rows = [(name, c, tot * 1000, mn * 1000, mx * 1000)
                for name, (c, tot, mn, mx) in _AGG.items()]
        dev_rows = [(name, c, tot_us / 1000.0)
                    for name, (c, tot_us) in _DEVICE_AGG.items()]
        mem_rows = [(name, peak[0]) for name, peak in _MEM_AGG.items()]
        mem_peak = dict(_MEM_STATE)
        if reset:
            _AGG.clear()
            _EVENTS.clear()
            _DEVICE_AGG.clear()
            _DEVICE_EVENTS.clear()
            _MEM_AGG.clear()
            _MEM_STATE.update(peak=0, peak_op=None)
    key = {"total": 2, "count": 1, "min": 3, "max": 4}.get(sort_by, 2)
    rows.sort(key=lambda r: r[key], reverse=not ascending)
    if format == "json":
        payload = {
            "host": [{"name": n, "count": c, "total_ms": tot, "min_ms": mn,
                      "max_ms": mx} for n, c, tot, mn, mx in rows],
            "device": sorted(
                ({"name": n, "count": c, "total_ms": tot}
                 for n, c, tot in dev_rows),
                key=lambda r: r["total_ms"], reverse=not ascending),
        }
        if memory:
            payload["memory"] = {
                "devices": memory_stats(),
                "observed_peak": mem_peak,
                "op_peak_live_bytes": {n: p for n, p in mem_rows},
                "largest_live_buffers": [
                    {"shape": list(shape), "dtype": dtype, "nbytes": nb}
                    for shape, dtype, nb in live_buffer_table(10)],
            }
        return json.dumps(payload)
    lines = [f"{'Name':<40}{'Count':>8}{'Total(ms)':>12}{'Min(ms)':>10}"
             f"{'Max(ms)':>10}", "=" * 80]
    for name, c, tot, mn, mx in rows:
        lines.append(f"{name[:39]:<40}{c:>8}{tot:>12.3f}{mn:>10.3f}{mx:>10.3f}")
    if dev_rows:
        dev_rows.sort(key=lambda r: r[2], reverse=not ascending)
        lines += ["", f"{'Device op':<48}{'Count':>8}{'Total(ms)':>12}",
                  "=" * 80]
        for name, c, tot in dev_rows:
            lines.append(f"{name[:47]:<48}{c:>8}{tot:>12.3f}")
    if memory:
        lines += ["", "Memory", "=" * 80]
        for dev, st in memory_stats().items():
            in_use = st.get("bytes_in_use", 0)
            peak = st.get("peak_bytes_in_use", in_use)
            lines.append(f"{dev:<40}{in_use / 2**20:>14.2f} MiB in use"
                         f"{peak / 2**20:>14.2f} MiB peak")
        if mem_peak["peak"]:
            lines.append(
                f"observed live-bytes peak: {mem_peak['peak'] / 2**20:.2f} "
                f"MiB at op {mem_peak['peak_op']}")
            mem_rows.sort(key=lambda r: -r[1])
            lines += ["", f"{'Op (peak live bytes at dispatch)':<48}"
                          f"{'MiB':>12}", "-" * 60]
            for name, peak_b in mem_rows[:15]:
                lines.append(f"{name[:47]:<48}{peak_b / 2**20:>12.2f}")
        lines += ["", f"{'Largest live buffers':<40}{'dtype':>10}"
                      f"{'MiB':>12}", "-" * 62]
        for shape, dtype, nbytes in live_buffer_table(10):
            lines.append(f"{str(shape)[:39]:<40}{dtype:>10}"
                         f"{nbytes / 2**20:>12.2f}")
    return "\n".join(lines)


class Scope:
    """RAII profiling scope (ProfileTask/ProfileEvent parity)."""

    def __init__(self, name="<unk>:"):
        self.name = name
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if _STATE["running"]:
            record_op(self.name, time.perf_counter() - self._t0)
        return False


profiler_scope = Scope

if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    # MXNET_PROFILER_MODE (env_var.md, default 0): 0 = symbolic/device
    # only (skip per-op imperative timing), 1 = all
    if os.environ.get("MXNET_PROFILER_MODE", "0") != "1":
        set_config(profile_imperative=False)
    start()
    atexit.register(dump)
