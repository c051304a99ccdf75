"""What every runner needs: where files are, the compile cache, the device
check, host spans, the profiler window, compile counting, the result line."""
from __future__ import annotations

import contextlib
import importlib
import json
import os
import sys
import threading
import time

CHIPBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(CHIPBENCH)


class NoChip(SystemExit):
    """jax found no TPU, or fewer chips than the cell asks for: exit code 2,
    no result line."""

    def __init__(self, why):
        print(f"chipbench: {why}", file=sys.stderr)
        super().__init__(2)


class Spec:
    """The data files of one cell, found by name under `root`."""

    def __init__(self, workload, root=CHIPBENCH):
        self.root = root
        self.cell = self.load("workloads", workload)
        self.cell["name"] = workload
        self.config = self.load("configs", self.cell["config"])
        self.traffic = self.load("traffic", self.cell["traffic"])
        self.peaks = self.load_file("peaks.json")

    def load_file(self, *parts):
        with open(os.path.join(self.root, *parts)) as f:
            return json.load(f)

    def load(self, kind, name):
        path = os.path.join(self.root, kind, name + ".json")
        if not os.path.isfile(path):
            raise SystemExit(f"chipbench: no {kind[:-1]} file {path}")
        return self.load_file(kind, name + ".json")

    def metric(self, name):
        return self.load("metrics", name)

    def peak(self, device_kind):
        if device_kind not in self.peaks:
            raise KeyError(
                f"device kind {device_kind!r} is not in peaks.json; a device "
                "without stated peaks has no roofline")
        return self.peaks[device_kind]


def module_of(kind, name, root=CHIPBENCH):
    """A reader, runner, reference or flops module, found by its file name:
    under `root` if the file is there (a cell added elsewhere brings its
    own), else under chipbench/."""
    path = os.path.join(root, kind, name + ".py")
    if root != CHIPBENCH and os.path.isfile(path):
        spec = importlib.util.spec_from_file_location(
            f"chipbench_added.{kind}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    return importlib.import_module(f"chipbench.{kind}.{name}")


def configure_compile_cache():
    """Before the program is imported: one fixed cache directory inside the
    checkout (or the one the environment gives), and no threshold under which
    a compiled program is thrown away — the ~136 small eager programs of a
    set-up each compile in under jax's default 1 s and would never be kept."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(CHECKOUT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def find_devices(chips, require_tpu=True):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"jax reports {devices[0].platform!r} devices, no TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), jax reports "
                     f"{len(devices)}")
    return devices[:chips]


class CompileWatch:
    """What jax itself reports: backend compiles and persistent-cache hits
    and misses. (The idea of `chip_smoke.CompileWatch`, kept here so that no
    PR to the program can change what the benchmark counts.)"""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "hits": self.hits, "misses": self.misses}

    def since(self, snap):
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}


class Spans:
    """Host spans recorded by the benchmark around its calls into the
    program: kept in memory on the host clock, and, while the profiler runs,
    written into its trace so that they share the device's clock."""

    def __init__(self):
        self.records = {}       # name -> [(start_s, duration_s), ...]
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name):
        note = None
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation(name)
            note.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.setdefault(name, []).append(
                (t0, time.perf_counter() - t0))
            if note is not None:
                note.__exit__(None, None, None)

    def wrap(self, obj, attr, name, on_call=None):
        """Put `obj.attr(...)` inside a span, from outside the program (an
        attribute on the instance; the class is untouched). Traced runs only:
        spans inside the program are the next `tracing` issue's."""
        inner = getattr(obj, attr)

        def call(*a, **kw):
            if on_call is not None:
                on_call(*a, **kw)
            with self.span(name):
                return inner(*a, **kw)

        setattr(obj, attr, call)

    def durations_ms(self, name, lo=None, hi=None):
        return [d * 1e3 for s, d in self.records.get(name, [])
                if (lo is None or s >= lo) and (hi is None or s + d <= hi)]


class Tracer:
    """The profiler over the first `seconds` of a window. `start` returns at
    once; the stop runs on a thread of its own, so that writing the trace
    does not stall the loop it measured."""

    def __init__(self, out_dir, seconds, spans):
        self.dir, self.seconds, self.spans = out_dir, seconds, spans
        self._thread = None
        self.clock = None        # the traced window on time.perf_counter()
        self.error = None

    def start(self):
        import jax

        jax.profiler.start_trace(self.dir)
        self.spans.annotate = True
        self._thread = threading.Thread(target=self._run, name="cb-tracer",
                                        daemon=True)
        self._thread.start()

    def _run(self):
        import jax

        try:
            with jax.profiler.TraceAnnotation("cb.window"):
                t_lo = time.perf_counter()
                time.sleep(self.seconds)
                self.clock = (t_lo, time.perf_counter())
            self.spans.annotate = False
            jax.profiler.stop_trace()
        except Exception as e:     # reported by finish(), never swallowed
            self.error = e

    def finish(self):
        """Wait for the stop and read the lanes back."""
        from chipbench.lib import trace

        self._thread.join(timeout=240.0)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop within 240 s")
        if self.error is not None:
            raise self.error
        return trace.load_lanes(trace.find_xplane(self.dir))


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else 0


def passed(check):
    """A compared number holds if it is at most its limit (at least, where
    the entry says ``at_least``)."""
    v, lim = check["value"], check["limit"]
    if v is None or v != v:
        return False
    return v >= lim if check.get("at_least") else v <= lim


def all_passed(checks):
    return bool(checks) and all(passed(c) for c in checks)


def report(result, checks):
    """The compared numbers as the last lines on stderr, the result as the
    last line on stdout (the compared numbers last in it)."""
    compared = {c["name"]: {"value": c["value"], "limit": c["limit"],
                            "ok": passed(c)} for c in checks}
    sys.stdout.flush()
    for c in checks:
        print(f"chipbench compared {c['name']}: value {c['value']!r} "
              f"{'>=' if c.get('at_least') else '<='} limit {c['limit']!r}"
              f"{'' if passed(c) else '  FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    result["compared"] = compared
    print(json.dumps(result), flush=True)
