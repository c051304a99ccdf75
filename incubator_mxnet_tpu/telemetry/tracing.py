"""Request/step-level span tracing + the crash flight recorder.

The registry (PR 2) answers *aggregate* questions — counters, histograms,
stage totals. This module answers the question the serving engine made
acute: "where did THIS request (or THIS step) spend its time?" It is a
Dapper-style tracer (Sigelman et al., 2010): every unit of work is a
**span** with a trace id shared by everything belonging to the same
request/step, a span id, and a parent id — so one slow TTFT p99 sample in
`bench_gpt_serve` decomposes into its queue wait, prefill, and per-step
decode segments instead of being one opaque number.

Design contract (same discipline as `stages.py`):

- **off** (`MXNET_TELEMETRY` unset, the default): every span probe —
  ``span()``, ``open_span()``, ``record_span()``, ``event()``,
  ``annotate()`` — is one module-global ``_ENABLED`` check returning a
  shared no-op singleton. No allocation, no clock read, no lock
  (`tests/test_tracing.py` counts the clock reads: none).
- **on** (`enable()` or any truthy ``MXNET_TELEMETRY``): a span reads
  ``perf_counter_ns`` once at each end (or takes a stamp its caller
  already read) and opens a `jax.profiler.TraceAnnotation` of its own
  name, so an armed run's spans are written into a live profiler
  session's own trace, on the device's clock. Its epoch-µs start, for
  the Chrome export, is derived from the same reading.
- **always on — the phase clock** (`StepClock`, `phase()`):
  the serving loop's boundaries are stamped once each with
  ``time.perf_counter()``; every boundary is also a `TraceAnnotation`
  (``mx.serve.*``, a no-op unless a profiler session is live), and each
  iteration that made progress and each retired request leaves one
  record in a bounded ring (`step_records()`, `request_records()`) that
  outlives the engine that wrote it.
- **host-side only**: spans are never created inside jitted bodies
  (lint FL008) and never captured by a trace — the serving engine's
  zero-steady-state-recompile guarantee is untouched.

Three ways to open a span:

- ``with tracing.span("serve.prefill", request=rid):`` — the blessed
  context-manager form (ambient: nested spans parent automatically via a
  thread-local stack);
- ``Tracer.start_span(...)`` — same semantics on an explicit tracer;
  MUST be used with ``with`` (lint FL008 flags a bare call);
- ``open_span(...)`` / ``Span.close()`` — explicit lifecycle for spans
  that cross function/thread boundaries (a serve request's root span is
  opened at submit on the client thread and closed at retire on the
  driver thread). Not ambient: an open_span never enters the TLS stack.

Finished spans land in per-thread ring buffers (bounded; merged on
read), so steady-state tracing is allocation-bounded and lock-free on
the hot path — exactly the registry's shard trick applied to spans.

Flight recorder: `flight_dump(reason)` snapshots the rings (recent
finished spans + still-open spans + orphan events + the armed chaos
schedule) into ``benchmark/flightrec_<reason>_<pid>.json`` so a crash
postmortem carries the last N spans of context. `ResilienceHandler`,
the serve driver thread, and the installed `sys.excepthook` all call
`maybe_flight_dump` — a no-op while tracing is off.
"""
from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

from collections import deque

from .locks import tracked_lock

__all__ = ["Span", "Tracer", "enable", "disable", "is_enabled", "span",
           "open_span", "record_span", "event", "annotate", "current_span",
           "StepClock", "phase", "launch_phase", "stamp",
           "add_request_record", "count", "step_records", "request_records",
           "PHASES", "LAUNCH_PARTS", "DRY_CAUSES", "STEP_RING_CAPACITY",
           "REQUEST_RING_CAPACITY",
           "current_trace_id", "new_trace_id", "finished_spans",
           "open_spans", "reset", "chrome_events", "chrome_trace",
           "dump_chrome", "flight_dump", "maybe_flight_dump",
           "register_flight_context", "RING_CAPACITY"]

RING_CAPACITY = 4096          # finished spans kept per writer thread
_FLIGHT_SPANS = 256           # most-recent spans a flight dump carries
STEP_RING_CAPACITY = 8192     # step records kept (19 min at 7 steps/s)
REQUEST_RING_CAPACITY = 4096  # request records kept
# the phases of one serving iteration, as a step record names them
PHASES = ("lock_wait", "admit", "prefill_launch", "prefill_readback",
          "decode_launch", "decode_readback", "emit", "eva_roll")
# the parts of ``decode_launch``, stamped inside it (`launch_phase`): step
# record fields beside the phases, in no series and not in `PHASES`
LAUNCH_PARTS = ("launch_prepare", "launch_key", "launch_upload",
                "launch_dispatch")
# why the device had nothing queued (`StepClock.watch_dry`): a second axis
# over the same wall, step record fields ``dry_<cause>``, never phases
DRY_CAUSES = ("chunk_fetch", "cold_fetch", "late_launch", "no_work")
_NO_PARTS = dict.fromkeys(LAUNCH_PARTS, 0.0)
_NO_DRY = {"dry_" + cause: 0.0 for cause in DRY_CAUSES}

_ENABLED = False
_LOCK = tracked_lock("telemetry.tracing", kind="lock")
_RINGS: list = []             # one deque per writer thread (merged reads)
_OPEN: dict = {}              # span_id -> still-open Span (flight recorder)
_ORPHAN_EVENTS: deque = deque(maxlen=512)   # events with no current span
_TLS = threading.local()
_IDS = random.Random()        # span/trace id entropy (host-side only)
_PREV_EXCEPTHOOK = None
# epoch µs at perf_counter 0: a span's Chrome timestamp is derived from
# its one perf_counter reading (host-device alignment does not rest on
# it: an armed span is also a TraceAnnotation in the profiler's trace)
_EPOCH_US = time.time() * 1e6 - time.perf_counter() * 1e6
_STEPS: deque = deque(maxlen=STEP_RING_CAPACITY)
_REQUESTS: deque = deque(maxlen=REQUEST_RING_CAPACITY)
_NOTE = None                  # jax.profiler.TraceAnnotation, on first use


def _now_us():
    """Epoch µs on the spans' clock (perf_counter plus the fixed offset)."""
    return time.perf_counter() * 1e6 + _EPOCH_US


def _note(name):
    """A `jax.profiler.TraceAnnotation`: a host span written into the
    profiler's own trace while a session is live, a no-op otherwise."""
    global _NOTE
    if _NOTE is None:
        import jax

        _NOTE = jax.profiler.TraceAnnotation
    return _NOTE(name)


def new_trace_id():
    """Fresh 64-bit correlation id (hex). One per request/step trace."""
    return f"{_IDS.getrandbits(64):016x}"


def _new_span_id():
    return f"{_IDS.getrandbits(32):08x}"


class _NullSpan:
    """Shared no-op span: what every probe returns while tracing is off
    (and what nested calls receive so call sites never branch)."""

    __slots__ = ()
    trace_id = None
    span_id = None
    name = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **attrs):
        return self

    def event(self, name, **attrs):  # noqa: ARG002
        return self

    def close(self, error=None, t_end=None):  # noqa: ARG002
        return self

    def __bool__(self):
        return False


_NULL_SPAN = _NullSpan()


class Span:
    """One timed unit of work. Created via `span()` (ambient context
    manager) or `open_span()` (explicit lifecycle); never construct
    directly."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "attrs",
                 "events", "t0_us", "t0_ns", "dur_ns", "thread", "lane",
                 "_ambient", "_note")

    def __init__(self, name, trace_id, parent_id, attrs, lane, ambient,
                 t0=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.attrs = attrs
        self.events: list = []
        # `t0`: a time.perf_counter() stamp the caller already read
        self.t0_ns = time.perf_counter_ns() if t0 is None else int(t0 * 1e9)
        self.t0_us = self.t0_ns / 1e3 + _EPOCH_US   # epoch µs, derived
        self.dur_ns = None
        self.thread = threading.current_thread().name
        self.lane = lane
        self._ambient = ambient
        # the same span in a live profiler session's own trace (a stamped
        # span starts in the past: its boundaries' `mx.*` annotations are
        # there already)
        self._note = None
        if t0 is None:
            self._note = _note(name)
            self._note.__enter__()
        with _LOCK:
            _OPEN[self.span_id] = self

    # -- context-manager (ambient) form -------------------------------------

    def __enter__(self):
        if self._ambient:
            stack = getattr(_TLS, "stack", None)
            if stack is None:
                stack = _TLS.stack = []
            stack.append(self)
        return self

    def __exit__(self, exc_type, exc, tb):  # noqa: ARG002
        if self._ambient:
            stack = getattr(_TLS, "stack", None)
            if stack and stack[-1] is self:
                stack.pop()
        self.close(error=exc)
        return False

    # -- shared surface ------------------------------------------------------

    @property
    def duration_s(self):
        """Span duration in seconds (None while still open)."""
        return None if self.dur_ns is None else self.dur_ns / 1e9

    def annotate(self, **attrs):
        self.attrs.update(attrs)
        return self

    def event(self, name, **attrs):
        """Point-in-time marker inside this span (Chrome 'instant')."""
        self.events.append((name, _now_us(), attrs))
        return self

    def close(self, error=None, t_end=None):
        """Stamp the duration (from `t_end`, a time.perf_counter() stamp
        the caller already read, else from the clock) and move the span
        to the finished ring. Idempotent (a double close keeps the first
        duration)."""
        if self.dur_ns is not None:
            return self
        self.dur_ns = (time.perf_counter_ns() if t_end is None
                       else int(t_end * 1e9)) - self.t0_ns
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
        if error is not None:
            self.attrs.setdefault("error", type(error).__name__)
            self.attrs.setdefault("error_msg", str(error)[:200])
        with _LOCK:
            _OPEN.pop(self.span_id, None)
        ring = getattr(_TLS, "ring", None)
        if ring is None:
            ring = _TLS.ring = deque(maxlen=RING_CAPACITY)
            with _LOCK:
                _RINGS.append(ring)
        ring.append(self)
        return self

    def to_dict(self):
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "ts_us": self.t0_us,
                "dur_us": None if self.dur_ns is None else self.dur_ns / 1e3,
                "thread": self.thread, "lane": self.lane,
                "attrs": dict(self.attrs),
                "events": [{"name": n, "ts_us": t, "attrs": a}
                           for n, t, a in self.events]}

    def __repr__(self):
        state = "open" if self.dur_ns is None \
            else f"{self.dur_ns / 1e3:.1f}us"
        return (f"<Span {self.name} trace={self.trace_id} "
                f"id={self.span_id} {state}>")


# ---------------------------------------------------------------------------
# probes (module surface — every call a dead branch while off)
# ---------------------------------------------------------------------------

def span(name, parent=None, trace_id=None, lane=None, t0=None, **attrs):
    """Open an ambient span as a context manager::

        with tracing.span("estimator.step", step=i):
            ...

    Nested calls parent automatically (thread-local stack). `parent`
    (a Span) or `trace_id` override the ambient parent — that is how
    work done on another thread joins a request's trace. `t0` is a
    ``time.perf_counter()`` stamp the caller already read (the span then
    reads no clock to start). Returns the shared no-op span while
    tracing is off."""
    if not _ENABLED:
        return _NULL_SPAN
    return _make_span(name, parent, trace_id, lane, attrs, ambient=True,
                      t0=t0)


def record_span(name, t0, t1, **attrs):
    """A finished span from two ``time.perf_counter()`` stamps the caller
    already read: parented like `span()`, reads no clock. The shared
    no-op span while tracing is off."""
    if not _ENABLED:
        return _NULL_SPAN
    return _make_span(name, None, None, None, attrs, ambient=False,
                      t0=t0).close(t_end=t1)


def open_span(name, parent=None, trace_id=None, lane=None, **attrs):
    """Open a span with EXPLICIT lifecycle — the caller must `close()`
    it. Never enters the ambient stack (safe to close from another
    thread). Use for spans that outlive a lexical scope, e.g. a serve
    request's root span (submit → retire)."""
    if not _ENABLED:
        return _NULL_SPAN
    return _make_span(name, parent, trace_id, lane, attrs, ambient=False)


def _make_span(name, parent, trace_id, lane, attrs, ambient, t0=None):
    if parent is None and trace_id is None:
        stack = getattr(_TLS, "stack", None)
        if stack:
            parent = stack[-1]
    if parent is not None and parent.trace_id is not None:
        trace_id = parent.trace_id
        parent_id = parent.span_id
        if lane is None:
            lane = parent.lane
    else:
        parent_id = None
        if trace_id is None:
            trace_id = new_trace_id()
    return Span(name, trace_id, parent_id, attrs, lane, ambient, t0)


def event(name, **attrs):
    """Record a point-in-time event on the CURRENT ambient span (or the
    orphan ring when no span is open — flight dumps still carry it)."""
    if not _ENABLED:
        return
    stack = getattr(_TLS, "stack", None)
    if stack:
        stack[-1].event(name, **attrs)
    else:
        _ORPHAN_EVENTS.append((name, _now_us(), attrs))


def annotate(**attrs):
    """Attach attributes to the current ambient span (no-op without
    one — annotations never raise from instrumentation sites)."""
    if not _ENABLED:
        return
    stack = getattr(_TLS, "stack", None)
    if stack:
        stack[-1].annotate(**attrs)


def current_span():
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


def current_trace_id():
    s = current_span()
    return s.trace_id if s is not None else None


class Tracer:
    """Object façade over the module tracer (reference-style handle for
    code that wants an injectable tracer). `start_span` is the
    context-manager API — lint FL008 flags calling it without `with`."""

    def start_span(self, name, parent=None, trace_id=None, lane=None,
                   **attrs):
        return span(name, parent=parent, trace_id=trace_id, lane=lane,
                    **attrs)

    def open_span(self, name, parent=None, trace_id=None, lane=None,
                  **attrs):
        return open_span(name, parent=parent, trace_id=trace_id,
                         lane=lane, **attrs)

    @property
    def enabled(self):
        return _ENABLED


# ---------------------------------------------------------------------------
# the phase clock: boundaries stamped once, always on
# ---------------------------------------------------------------------------

class StepClock:
    """The stamps of one iteration of the serving loop::

        with tracing.StepClock(waited_since, queued=n) as clock:

    A boundary is one ``time.perf_counter()`` reading, taken when a phase
    ENDS (`lap`): the phase is charged the time since the boundary
    before it (`cursor`), so consecutive phases share their stamp and
    nothing between them is clocked twice. The iteration's start and end
    are two more readings; what follows the last `lap` is in ``wall`` and
    in no phase (`step_records` readers watch that share). `waited_since`
    is the stamp at which the caller began to wait for the lock it now
    holds (charged to ``lock_wait``, outside ``wall``). The armed
    ``serve.step`` span (`attrs` are its attributes) takes its start and
    end from the same two readings. While the clock is open it is the
    calling thread's current one: `phase()` / `stamp()` beneath find it.
    An iteration that set ``progressed`` leaves one step record.

    Beside the phases, and in none of them: ``parts`` (`LAUNCH_PARTS`, the
    boundaries inside ``decode_launch``: `launch_phase`) and ``dry``, the
    intervals ``[t0, t1, cause]`` in which the device had nothing queued
    and which a launch of this iteration ended (`watch_dry`)."""

    __slots__ = ("t_start", "t_end", "cursor", "seconds", "parts", "dry",
                 "counts", "progressed", "span", "_open", "_note", "_prev",
                 "_dry", "_probe", "_cause")

    def __init__(self, waited_since=None, **attrs):
        self._open = (waited_since, attrs)
        self.counts = {}
        self.progressed = False
        self.seconds = dict.fromkeys(PHASES, 0.0)
        self.parts = _NO_PARTS.copy()
        self.dry = []
        self.t_end = None
        self._dry = None

    def __enter__(self):
        waited_since, attrs = self._open
        self._prev = getattr(_TLS, "clock", None)
        _TLS.clock = self
        self._note = _note("mx.serve.step")
        self._note.__enter__()
        self.t_start = self.cursor = time.perf_counter()
        if waited_since is not None:
            self.seconds["lock_wait"] = self.t_start - waited_since
        self.span = span("serve.step", t0=self.t_start, **attrs)
        self.span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t_end = time.perf_counter()
        self.span.close(error=exc, t_end=self.t_end)   # the stamp, not a read
        self.span.__exit__(exc_type, exc, tb)
        self._note.__exit__(None, None, None)
        _TLS.clock = self._prev
        if self.progressed and exc_type is None:
            rec = {"t_start": self.t_start,
                   "wall": self.t_end - self.t_start, **self.seconds,
                   **self.parts, **_NO_DRY, "dry": self.dry, **self.counts}
            for t0, t1, cause in self.dry:
                rec["dry_" + cause] += t1 - t0
            with _LOCK:
                _STEPS.append(rec)
        return False

    def lap(self, field, part=None):
        """A boundary: charge `field` (and `part`, one of `LAUNCH_PARTS`
        inside it) the time since the last one. Returns the stamp."""
        now = time.perf_counter()
        self.seconds[field] += now - self.cursor
        if part is not None:
            self.parts[part] += now - self.cursor
        self.cursor = now
        return now

    def watch_dry(self, state, probe, cause):
        """Arm this iteration's account of when the device stood dry.
        `state` is the engine's open interval ``[t0, cause]`` (``t0`` None:
        none open), a list its owner keeps between iterations and opens
        itself where a blocking fetch returns with nothing queued behind it;
        `probe()` says, without blocking, whether everything the engine
        launched has finished; `cause` is what an interval that a launch
        site opens (`launching`) is charged to."""
        self._dry, self._probe, self._cause = state, probe, cause

    def launching(self):
        """A launch site, at the boundary just taken and before its jitted
        call: if no interval is open and the device has nothing left to run,
        one opens here. The device went dry at some moment before this
        boundary, which nothing read: the interval is a lower bound."""
        state = self._dry
        if state is not None and state[0] is None and self._probe():
            state[:] = self.cursor, self._cause

    def launched(self):
        """The jitted call returned (the boundary its phase just took): the
        device has work again, and an open interval closes at that stamp,
        charged to this iteration."""
        state = self._dry
        if state is not None:
            if state[0] is not None:
                self.dry.append([state[0], self.cursor, state[1]])
                state[0] = None
            # from here on the iteration has launched: a launch site that
            # finds the device dry again was late, whatever came before
            self._cause = "late_launch"


class phase:
    """One boundary of the loop, always on::

        with tracing.phase("mx.serve.decode.launch", "decode_launch"):

    `name` is a `TraceAnnotation` from enter to exit (the span in the
    profiler's trace); at exit `field` is charged on the thread's open
    `StepClock` (none open, or no field: no reading). `timed`: stamp both
    ends itself and keep ``seconds`` (a phase outside any step, like the
    driver's back-off sleep). A phase that queues work on the device marks
    the place with `site()`, just before its jitted call."""

    __slots__ = ("_field", "_site", "_note", "_t0", "seconds")

    def __init__(self, name, field=None, timed=False):
        self._field = field
        self._site = False
        self._note = _note(name)
        self._t0 = timed or None          # None: untimed; else the start
        self.seconds = None

    def __enter__(self):
        self._note.__enter__()
        if self._t0 is not None:
            self._t0 = time.perf_counter()
        return self

    def site(self):
        """The launch site: what follows is the phase's jitted call. One
        boundary of its own (charged to the phase's field like its end), at
        which the clock's dry account looks (`StepClock.launching`); the
        phase's end then closes what is open (`launched`)."""
        clock = getattr(_TLS, "clock", None)
        if clock is not None and self._field is not None:
            clock.lap(self._field)
            clock.launching()
            self._site = True

    def __exit__(self, exc_type, exc, tb):
        if self._field is not None:
            clock = getattr(_TLS, "clock", None)
            if clock is not None:
                clock.lap(self._field)
                if self._site:
                    clock.launched()
        if self._t0 is not None:
            self.seconds = time.perf_counter() - self._t0
        self._note.__exit__(None, None, None)
        return False


_LAUNCH = "mx.serve.decode.launch"
_LAUNCH_SPANS = tuple(f"{_LAUNCH}.{part[len('launch_'):]}"
                      for part in LAUNCH_PARTS)
_LAUNCH_SITE = LAUNCH_PARTS.index("launch_dispatch")   # the jitted call


class launch_phase(phase):
    """``mx.serve.decode.launch`` with its four parts stamped inside it::

        with tracing.launch_phase() as boundary:
            ...                 # prepare: parameters, pools, the page table
            boundary()
            ...                 # key: the key maker's eager fold_in
            boundary()
            ...                 # upload: the host arrays' copies
            boundary()          # the launch site
            ...                 # dispatch: the jitted call
            boundary()
            ...                 # the launch's tail: in no part

    Each part is a `TraceAnnotation` ``mx.serve.decode.launch.<part>`` nested
    in the phase's own, and each ``boundary()`` one reading of the open
    clock, charged to ``decode_launch`` and to that one of `LAUNCH_PARTS`.
    The third boundary is where the dry account looks (the end of the
    uploads, just before the jitted call), the fourth where it closes."""

    __slots__ = ("_clock", "_at", "_part")

    def __init__(self):
        super().__init__(_LAUNCH, "decode_launch")

    def __enter__(self):
        super().__enter__()
        self._clock = getattr(_TLS, "clock", None)
        self._at = 0
        self._part = _note(_LAUNCH_SPANS[0])
        self._part.__enter__()
        return self._boundary

    def _boundary(self):
        at, clock = self._at, self._clock
        self._part.__exit__(None, None, None)
        self._part = None
        if clock is not None:
            clock.lap(self._field, LAUNCH_PARTS[at])
            if at == _LAUNCH_SITE:
                clock.launched()
        self._at = at = at + 1
        if at < len(LAUNCH_PARTS):
            self._part = _note(_LAUNCH_SPANS[at])
            self._part.__enter__()
            if at == _LAUNCH_SITE and clock is not None:
                clock.launching()

    def __exit__(self, exc_type, exc, tb):
        if self._part is not None:        # a part that raised
            self._part.__exit__(None, None, None)
        return super().__exit__(exc_type, exc, tb)


def stamp():
    """The last boundary's stamp on the thread's open clock (no reading);
    with no clock open, the time."""
    clock = getattr(_TLS, "clock", None)
    return time.perf_counter() if clock is None else clock.cursor


def count(**fields):
    """Add to the counts of the thread's open step clock: they become
    fields of its step record (no clock open: nothing)."""
    clock = getattr(_TLS, "clock", None)
    if clock is not None:
        for k, v in fields.items():
            clock.counts[k] = clock.counts.get(k, 0) + v


def add_request_record(**rec):
    """One retired request (``time.perf_counter()`` stamps and counts,
    see `request_records`); ``t_finish`` is the thread's last boundary."""
    rec["t_finish"] = stamp()
    with _LOCK:
        _REQUESTS.append(rec)


def _window(ring, key, since, until):
    with _LOCK:
        out = list(ring)
    return [dict(r) for r in out
            if (since is None or r[key] >= since)
            and (until is None or r[key] < until)]


def step_records(since=None, until=None):
    """Copies of the step records whose ``t_start`` lies in ``[since,
    until)`` (``time.perf_counter()`` seconds; None: unbounded), oldest
    first. One record per iteration that made progress: ``t_start``,
    seconds of each of `PHASES` and of ``wall`` (start to end, lock held;
    ``lock_wait`` lies before it), counts ``chunks``, ``decoding`` (active
    slots in the decode launch), ``prefilling`` and ``queued`` (at the
    iteration's end), ``pages_live`` and ``pages_view`` (of the decode
    launch, else 0: KV pages under the decoding slots' positions, and
    ``max_slots x pages_per_slot`` — what decode's attention reads,
    against what a gathered view of every slot holds); a family may add
    counts of its own (``moe_*``: `serve/mla.py`; ``state_resets``:
    `serve/ssm.py`).

    ``mode`` says how the iteration's decode launch was made: ``"ahead"``,
    queued behind a step the host had not fetched yet (the device goes from
    one to the next if the host was in time); ``"cold"``, with nothing in
    flight (the device waits for this launch); None, no decode launch (the
    iteration may still have fetched one). ``overshoot`` counts the rows of
    the step fetched here whose request had already ended when they ran
    (an EOS is learnt a step late; their tokens are dropped).

    The seconds of `LAUNCH_PARTS` split ``decode_launch`` where the work
    happens (`SlotDecoder.decode_step`): ``launch_prepare`` is the host's
    bookkeeping, from the boundary before the launch to the key (the
    scheduler's page mapping, its copy of the last tokens and its row
    list; parameter refresh, pools, the page table) and again after the
    call (the scheduler's update of the rows it launched); ``launch_key``
    is the key maker's eager ``fold_in``, ``launch_upload`` the host
    arrays' copies, ``launch_dispatch`` the jitted call from entry to
    return. They add up to ``decode_launch`` less the engine's counters
    after the call.

    ``dry`` lists the intervals ``[t0, t1, cause]`` (``perf_counter``
    seconds, usually none) in which the device had nothing queued and which
    a launch of this iteration ended; ``dry_<cause>`` are their seconds by
    `DRY_CAUSES`: ``chunk_fetch`` (a prompt's final chunk was fetched: the
    queue drained to hand out a first token), ``cold_fetch`` (a decode
    step was fetched with none launched behind it), ``late_launch`` (a
    launch site found everything it had queued already finished: opened at
    the boundary taken there, so a lower bound on the time the device
    stood dry) and ``no_work`` (no slot occupied and the queue empty: the
    interval runs across the driver's back-off until the next launch). A
    second axis over the same wall: in no phase, not in `PHASES`, and an
    interval is charged once, to the iteration that closed it, wherever it
    began. ``mode == "ahead"`` goes with ``dry_cold_fetch`` and
    ``dry_no_work`` of 0.0 (something was in flight); a ``"cold"`` launch
    ends an interval of one of the causes. A slots object that computes on
    the host keeps no such account (all 0.0).

    The ring is the module's, not the engine's: it outlives shutdown and
    deletion of whatever wrote it, holds the newest `STEP_RING_CAPACITY`
    records and drops the oldest."""
    return _window(_STEPS, "t_start", since, until)


def request_records(since=None, until=None):
    """Copies of the request records whose ``t_submit_call`` lies in
    ``[since, until)``, oldest first (by retirement). One record per
    request that left the engine: ``id`` (the ``request=`` attribute of
    its spans), ``trace_id`` (None unless armed), stamps ``t_submit_call``
    (entry of `ServeEngine.submit`), ``t_enqueued`` (lock held, queued),
    ``t_admit`` (start of the iteration that gave it a slot) and
    ``t_first_token`` (None where it never got there), ``t_finish``,
    ``chunks``, ``shared_tokens``, ``tokens``, ``state``. Newest
    `REQUEST_RING_CAPACITY` kept, oldest dropped; outlives the engine."""
    return _window(_REQUESTS, "t_submit_call", since, until)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def enable():
    """Arm span recording (idempotent) and install the crash excepthook
    so an unhandled exception leaves a flight-recorder dump behind."""
    global _ENABLED, _PREV_EXCEPTHOOK
    with _LOCK:
        already = _ENABLED
        _ENABLED = True
    if not already and _PREV_EXCEPTHOOK is None:
        _PREV_EXCEPTHOOK = sys.excepthook
        sys.excepthook = _crash_excepthook


def disable():
    """Disarm: every probe goes back to one `_ENABLED` check. Recorded
    spans stay readable until `reset()`."""
    global _ENABLED, _PREV_EXCEPTHOOK
    with _LOCK:
        _ENABLED = False
    if _PREV_EXCEPTHOOK is not None:
        sys.excepthook = _PREV_EXCEPTHOOK
        _PREV_EXCEPTHOOK = None


def is_enabled():
    return _ENABLED


def reset():
    """Drop every recorded span, event, step and request record (tests)."""
    with _LOCK:
        rings = list(_RINGS)
        _OPEN.clear()
        _STEPS.clear()
        _REQUESTS.clear()
    for r in rings:
        r.clear()
    _ORPHAN_EVENTS.clear()


def finished_spans(trace_id=None):
    """Merged finished spans across all threads, start-ordered; filter
    by `trace_id` to reconstruct one request/step."""
    with _LOCK:
        rings = list(_RINGS)
    out = []
    for r in rings:
        out.extend(list(r))
    if trace_id is not None:
        out = [s for s in out if s.trace_id == trace_id]
    out.sort(key=lambda s: s.t0_us)
    return out


def open_spans():
    """Spans still open right now (crash context: the work that was
    in flight)."""
    with _LOCK:
        return list(_OPEN.values())


# ---------------------------------------------------------------------------
# Chrome-trace / Perfetto export (epoch µs; profiler.py rebases its device
# lanes onto the same epoch by an anchor: TELEMETRY.md has its error)
# ---------------------------------------------------------------------------

_SPAN_PID = 2                 # host op dispatch owns pid 0, device 1000+


def chrome_events(spans=None):
    """Chrome-trace events for `spans` (default: every finished span).

    Lanes: spans carrying a ``lane`` (e.g. serve requests get
    ``"req <id>"``) each get their own tid with a thread_name metadata
    row — one horizontal lane per request in Perfetto; unlaned spans
    share a lane per OS thread. Timestamps are epoch-µs derived from
    each span's perf_counter reading; `profiler._ingest_device_trace`
    rebases XLA device events onto an epoch anchor of its own (its error
    is in TELEMETRY.md; an armed span is also in the profiler's trace
    itself, where nothing has to be rebased)."""
    if spans is None:
        spans = finished_spans()
    lanes: dict = {}

    def lane_tid(s):
        key = s.lane if s.lane is not None else f"thread {s.thread}"
        if key not in lanes:
            lanes[key] = len(lanes) + 1
        return lanes[key]

    events = []
    for s in spans:
        tid = lane_tid(s)
        args = {"trace_id": s.trace_id, "span_id": s.span_id}
        if s.parent_id:
            args["parent_id"] = s.parent_id
        args.update({k: str(v)[:120] for k, v in s.attrs.items()})
        events.append({"name": s.name, "ph": "X", "pid": _SPAN_PID,
                       "tid": tid, "ts": s.t0_us,
                       "dur": (s.dur_ns or 0) / 1e3, "args": args})
        for name, ts, attrs in s.events:
            events.append({"name": name, "ph": "i", "s": "t",
                           "pid": _SPAN_PID, "tid": tid, "ts": ts,
                           "args": {k: str(v)[:120]
                                    for k, v in attrs.items()}})
    meta = [{"name": "process_name", "ph": "M", "pid": _SPAN_PID,
             "args": {"name": "host: spans"}}]
    for key, tid in lanes.items():
        meta.append({"name": "thread_name", "ph": "M", "pid": _SPAN_PID,
                     "tid": tid, "args": {"name": str(key)}})
    return meta + events


def chrome_trace(include_device=True, spans=None):
    """One Chrome-trace payload: host spans (+ their instant events)
    merged with the XLA device lanes `profiler.py` captured on the last
    `profiler.stop()`, both in epoch µs (the device side through the
    profiler's anchor, see TELEMETRY.md for its measured error)."""
    events = chrome_events(spans)
    if include_device:
        from .. import profiler

        events = events + profiler.device_events()
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def dump_chrome(path, include_device=True):
    """Write `chrome_trace()` as JSON (open in Perfetto:
    https://ui.perfetto.dev → Open trace file). Returns the path."""
    with open(path, "w") as f:
        json.dump(chrome_trace(include_device=include_device), f)
    return path


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _flight_dir():
    d = os.environ.get("MXNET_FLIGHTREC_DIR")
    if d:
        return d
    return "benchmark" if os.path.isdir("benchmark") else "."


_FLIGHT_CONTEXT = {}          # name -> probe() returning a JSON-able dict
_RANK_STAMP = None            # set by telemetry.fleet on multi-rank runs:
                              # rank-stamps default flightrec filenames so
                              # a shared dir keeps every rank's dump apart


def register_flight_context(name, probe):
    """Attach a subsystem state probe to every flight dump: ``probe()``
    returns a JSON-able dict (or None to skip — the weakly-bound-source
    idiom) snapshotted into ``payload["context"][name]`` at crash time.
    The serving gateway registers its queue/slot state here so a crash
    dump shows WHAT was queued where, not just which spans were open.
    Re-registering a name replaces the previous probe."""
    _FLIGHT_CONTEXT[str(name)] = probe


def _flight_context():
    out = {}
    for name, probe in list(_FLIGHT_CONTEXT.items()):
        try:
            state = probe()
        except Exception as e:  # noqa: FL006 — best-effort context, never mask the dump
            state = {"probe_error": f"{type(e).__name__}: {e}"[:200]}
        if state is not None:
            out[name] = state
    return out


def flight_dump(reason, exc=None, path=None):
    """Snapshot the last `_FLIGHT_SPANS` finished spans, every still-open
    span (the in-flight work at crash time), orphan events, and the armed
    chaos schedule into ``flightrec_<reason>_<pid>.json``. Returns the
    written path. The file is overwritten per (reason, pid) — bounded
    artifacts, the LAST crash wins."""
    spans = finished_spans()[-_FLIGHT_SPANS:]
    payload = {
        "reason": reason,
        "pid": os.getpid(),
        "wall_time_us": time.time() * 1e6,
        "error": None if exc is None else {
            "type": type(exc).__name__, "message": str(exc)[:500]},
        "open_spans": [s.to_dict() for s in open_spans()],
        "spans": [s.to_dict() for s in spans],
        "orphan_events": [{"name": n, "ts_us": t, "attrs": a}
                          for n, t, a in list(_ORPHAN_EVENTS)],
        "context": _flight_context(),
    }
    try:
        from ..fault.injection import schedule_info

        payload["fault_schedule"] = schedule_info()
    except Exception:  # noqa: FL006 — best-effort context, never mask the dump
        payload["fault_schedule"] = {}
    if path is None:
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in str(reason))[:60]
        stamp = (f"rank{_RANK_STAMP:03d}_" if _RANK_STAMP is not None
                 else "")
        path = os.path.join(_flight_dir(),
                            f"flightrec_{safe}_{stamp}{os.getpid()}.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)
    import logging

    logging.getLogger("incubator_mxnet_tpu.telemetry").warning(
        "flight recorder: dumped %d spans (+%d open) to %s (reason: %s)",
        len(spans), len(payload["open_spans"]), path, reason)
    return path


def maybe_flight_dump(reason, exc=None):
    """The hook form: dump only when tracing is armed (a disabled tracer
    has nothing to record and must stay zero-cost). Never raises — a
    broken dump must not mask the crash it documents."""
    if not _ENABLED:
        return None
    try:
        return flight_dump(reason, exc=exc)
    except Exception as e:
        from ..fault.retry import suppressed

        suppressed("tracing.flight_dump", e)
        return None


def _crash_excepthook(exc_type, exc, tb):
    maybe_flight_dump("crash", exc=exc)
    if _PREV_EXCEPTHOOK is not None:
        _PREV_EXCEPTHOOK(exc_type, exc, tb)
    else:  # pragma: no cover - excepthook replaced underneath us
        sys.__excepthook__(exc_type, exc, tb)
