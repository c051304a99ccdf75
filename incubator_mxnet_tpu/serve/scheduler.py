"""Iteration-level scheduler for `mx.serve` (the host half).

Continuous (iteration-level) batching, Orca-style, over the PAGED
engine: the unit of scheduling is one decode STEP, and prefill is
CHUNKED — a long prompt no longer monopolizes the loop for a whole
monolithic prefill. Every `step()`:

1. fires the ``serve_step`` fault seam (`MXNET_FAULT_INJECT` chaos);
2. expires requests past their deadline (queued, prefilling AND
   decoding);
3. admits waiting requests into free slots: the prefix cache is probed
   for a shared page-aligned prompt prefix (pages attached read-only,
   ``mx_serve_prefix_hits_total``), private pages for the rest of the
   request are reserved up front (so decode can never hit a mid-flight
   page OOM), and the request enters the *prefilling* phase;
4. runs ONE prefill chunk for every prefilling slot
   (``mx_serve_prefill_chunks_total``) — the final chunk samples the
   request's first token (TTFT is measured here) and flips the slot to
   *decoding*; the completed prompt's full pages are registered in the
   prefix cache for later requests;
5. LAUNCHES one batched decode step over all decoding slots, behind the
   step launched an iteration ago, whose tokens the host has not fetched
   yet: a slot that goes on takes its last token from that step's output
   on the device, so the device goes from step to step while the host
   prepares the next launch (`_launch`). A slot whose token on its way is
   its ``max_new``-th is freed as soon as that step is queued;
6. fetches the step launched an iteration ago, hands its tokens out and
   retires slots whose request hit EOS — pages are dereferenced (shared
   pages survive in the cache) and the slot is immediately free,
   mid-flight (`_land`). An EOS is learnt one step late: the row already
   launched for that slot is an overshoot, and its token is dropped;
7. refreshes the queue-depth / occupancy gauges
   (``mx_serve_page_occupancy`` is pull-based off the allocator, shared
   pages counted once).

**The step in flight.** Between two iterations one decode step is queued
on the device and unfetched (`_flight`). Whatever changes a slot outside
this order first fetches it and hands its tokens out (`settle`): a
deadline that expired, `preempt`, `close`. `abandon` drops it (the engine
is dead and its requests are re-owned). An engine with a step in flight
is not `idle`. A slots object whose ``decode_step`` computes on the host
and returns numpy (the tests' stand-ins) has nothing in flight: its
tokens are handed out in the iteration that made them.

Admission policies: ``"fifo"`` (arrival order) and ``"sjf"`` — which
under chunked prefill orders by *remaining prefill chunks* (a long
prompt whose prefix is already cached is cheaper than a shorter cold
one), with prompt length as the tie-break so the classic
shortest-prompt-first behavior is preserved when the cache is cold.

Admission is page-aware: when the pool cannot cover a request even
after dropping unused prefix-cache entries, the request simply stays
queued until running requests retire — pages referenced by live
requests are NEVER silently evicted (`PagePoolExhausted` is raised only
at submit, for requests that could never fit an empty pool).

Backpressure stays LOUD: a full queue raises :class:`QueueFull` at
``submit``; deadline expiry raises :class:`DeadlineExceeded` (a
``TimeoutError``); both classify as ``'retryable'`` under
`fault.retry.classify_exception`.

All mutable slot state (``pos``/``last``/``active``/page tables) lives
HOST-side as numpy arrays passed into the compiled step — the loop
never branches on device values (lint FL007/FL009), and the one
device→host sync per step is the token fetch clients need anyway, made
after the next step is queued.
"""
from __future__ import annotations

import collections
import queue as _queue
import threading
import time

import numpy as onp

from ..telemetry import anatomy, capacity, registry, tracing
from .engine import PagePoolExhausted, SlotDecoder

__all__ = ["QueueFull", "DeadlineExceeded", "EngineClosed", "Request",
           "Scheduler", "POLICIES", "TTFT", "TOKENS", "QUEUE_DEPTH",
           "SLOT_OCCUPANCY", "EVICTIONS", "PREFIX_HITS", "PREFILL_CHUNKS"]

POLICIES = ("fifo", "sjf")

# -- metric series (ISSUE 4 + ISSUE 6 telemetry contract; SERVING.md) -------
TTFT = registry.histogram(
    "mx_serve_ttft_seconds",
    "time-to-first-token: submit() to the final prefill chunk's sampled "
    "token")
TOKENS = registry.counter(
    "mx_serve_tokens_total", "tokens generated by the serving engine")
QUEUE_DEPTH = registry.gauge(
    "mx_serve_queue_depth", "requests waiting for a slot")
SLOT_OCCUPANCY = registry.gauge(
    "mx_serve_slot_occupancy",
    "fraction of slots occupied (prefilling + decoding) [0, 1]")
EVICTIONS = registry.counter(
    "mx_serve_evictions_total",
    "slots freed (EOS / length / deadline / shutdown)")
PREFIX_HITS = registry.counter(
    "mx_serve_prefix_hits_total",
    "admissions that reused shared-prefix KV pages from the cache")
PREFIX_TOKENS = registry.counter(
    "mx_serve_prefix_tokens_total",
    "prompt tokens whose prefill was skipped via shared-prefix reuse")
PREFILL_CHUNKS = registry.counter(
    "mx_serve_prefill_chunks_total",
    "prefill chunks executed (long prompts span several, interleaved "
    "with decode steps)")
DECODE_LAUNCHES = {
    mode: registry.counter(
        "mx_serve_decode_launches_total",
        "decode programs launched: `ahead`, queued while the step before "
        "was still unfetched (the device goes from one to the next); "
        "`cold`, with nothing in flight (the device waits for the launch)",
        labels={"mode": mode})
    for mode in ("ahead", "cold")}
OVERSHOOT_ROWS = registry.counter(
    "mx_serve_decode_overshoot_rows_total",
    "rows of a decode launch made for a request that had already emitted "
    "its EOS (learnt one step late): the row's token is dropped")
PREFILL_FETCHES = {
    kind: registry.counter(
        "mx_serve_prefill_fetches_total",
        "prefill chunks by what became of the token they sampled: `final`, "
        "a prompt's last chunk, fetched (the request's first token); "
        "`skipped`, an intermediate chunk, never fetched",
        labels={"kind": kind})
    for kind in ("final", "skipped")}
SPEC_DRAFTED = registry.counter(
    "mx_serve_spec_drafted_tokens_total",
    "draft tokens proposed to the verify program (speculative decoding)")
SPEC_ACCEPTED = registry.counter(
    "mx_serve_spec_accepted_tokens_total",
    "draft tokens accepted by the verify program (speculative decoding)")
# the step clock's phases as running totals: with `idle` (the driver's
# back-off sleep, serve/api.py) they add up to the driving thread's wall
STEP_SECONDS = {
    ph: registry.counter(
        "mx_serve_step_seconds_total",
        "seconds of the thread that drives the engine, by phase of "
        "Scheduler.step (idle: the driver's back-off sleep)",
        labels={"phase": ph})
    for ph in tracing.PHASES + ("idle",)}
# the step records' dry intervals as running totals (`tracing.DRY_CAUSES`):
# over wall time, chunk_fetch + cold_fetch + late_launch is the share of the
# chip the host wastes, no_work its spare capacity
DEVICE_DRY = {
    cause: registry.counter(
        "mx_serve_device_dry_seconds_total",
        "seconds the device had nothing queued, by cause: chunk_fetch (a "
        "prompt's final chunk fetched), cold_fetch (a decode step fetched "
        "with none launched behind it), late_launch (a launch found its "
        "queue already finished: a lower bound), no_work (the engine was "
        "empty)",
        labels={"cause": cause})
    for cause in tracing.DRY_CAUSES}
SUBMIT_LOCK_WAIT = registry.histogram(
    "mx_serve_submit_lock_wait_seconds",
    "entry of ServeEngine.submit to the request queued: the wait for the "
    "engine lock")
QUEUE_WAIT = registry.histogram(
    "mx_serve_queue_wait_seconds",
    "request queued to the start of the iteration that gave it a slot")


class QueueFull(RuntimeError):
    """Admission queue at capacity — loud backpressure, nothing is
    silently dropped. Retryable under `fault.retry.classify_exception`."""


class DeadlineExceeded(TimeoutError):
    """The request's deadline passed before it finished (queued or
    mid-decode). TimeoutError ⇒ 'retryable' classification."""


class EngineClosed(RuntimeError):
    """The engine is shut down (or draining) and rejects new work."""


_DONE = object()          # stream sentinel
_NULL = tracing._NULL_SPAN   # default for closed-span pops in _trace_close


class Request:
    """One in-flight generation request (also the user-facing handle).

    Tokens stream into ``_stream`` as they are produced; ``wait()``
    blocks until completion; ``result()`` returns the generated tokens
    (raising the request's error if it failed)."""

    __slots__ = ("id", "prompt", "max_new", "temperature", "eos_id",
                 "deadline", "submit_t", "first_token_t", "finish_t",
                 "tokens", "state", "error", "error_class", "slot",
                 "pages", "shared_tokens", "prefill_pos", "chunks_done",
                 "spec_reserved", "tenant", "prefill_only", "first_token",
                 "inflight", "anatomy", "trace_id", "t_submit_call",
                 "t_enqueued", "t_admit", "t_first_token", "_spans",
                 "_stream", "_done")

    def __init__(self, rid, prompt, max_new, temperature, eos_id,
                 deadline, parent_span=None, tenant=None):
        self.id = rid
        self.tenant = tenant              # cost-ledger attribution
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature
        self.eos_id = eos_id
        self.deadline = deadline          # absolute monotonic, or None
        self.submit_t = None
        self.first_token_t = None
        self.finish_t = None
        self.tokens = []
        self.state = "queued"             # queued|running|done|failed
        self.error = None
        self.error_class = None
        self.slot = None
        self.pages = None                 # KV pool pages mapped at admit
        self.shared_tokens = 0            # prefix tokens reused from cache
        self.prefill_pos = 0              # prompt tokens already in cache
        self.chunks_done = 0
        self.spec_reserved = 0            # budgeted-but-unallocated pages
        self.prefill_only = False         # disagg handoff segment
        self.first_token = None           # sampled on the prefill side
        self.inflight = 0                 # tokens launched, not yet fetched
        self.anatomy = None               # gateway's anatomy record
        # the request record's stamps, on the step clock's clock (the
        # performance counter; `submit_t` and the deadline stay monotonic)
        self.t_submit_call = self.t_enqueued = None
        self.t_admit = self.t_first_token = None
        # one trace per request (Dapper-style): the root span opens at
        # submit on the client thread and closes at retire on whichever
        # thread drives the engine — explicit lifecycle, never ambient.
        # With `parent_span` (the gateway's per-request root) the engine
        # span JOINS that trace instead of starting its own. All no-op
        # singletons while tracing is off.
        root = tracing.open_span("serve.request", parent=parent_span,
                                 lane=f"req {rid}", request=rid,
                                 prompt_len=int(prompt.size),
                                 max_new=max_new)
        self.trace_id = root.trace_id
        self._spans = {"request": root,
                       "queue": tracing.open_span("serve.queue",
                                                  parent=root)}
        # bounded by max_new tokens + one sentinel per request
        self._stream = _queue.Queue()   # noqa: FL011
        self._done = threading.Event()

    # -- handle surface ----------------------------------------------------

    @property
    def done(self):
        return self._done.is_set()

    @property
    def ttft(self):
        """Seconds from submit to first token (None before it exists)."""
        if self.submit_t is None or self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    def wait(self, timeout=None):
        return self._done.wait(timeout)

    def result(self):
        """Generated tokens (list of ints). Raises the failure if the
        request did not complete."""
        if not self._done.is_set():
            raise RuntimeError(
                f"request {self.id} not finished (state={self.state}); "
                "wait() on it or drive the engine")
        if self.error is not None:
            raise self.error
        return list(self.tokens)

    # -- scheduler side ----------------------------------------------------

    def _emit(self, tok, now):
        if self.first_token_t is None:
            self.first_token_t = now
            self.t_first_token = tracing.stamp()   # its read-back's end
            TTFT.observe(now - self.submit_t)
        self.tokens.append(tok)
        self._stream.put(tok)
        TOKENS.inc()

    def _trace_close(self, error=None):
        """Close whatever lifecycle spans are still open, queue→prefill→
        decode→root (idempotent; every span is a no-op singleton when
        tracing is off)."""
        spans = self._spans
        if "request" in spans and self.t_enqueued is not None:
            # retirement (finished, failed, preempted or handed on): the
            # always-on record, once
            tracing.add_request_record(
                id=self.id, trace_id=self.trace_id,
                t_submit_call=self.t_submit_call,
                t_enqueued=self.t_enqueued, t_admit=self.t_admit,
                t_first_token=self.t_first_token, chunks=self.chunks_done,
                shared_tokens=self.shared_tokens, tokens=len(self.tokens),
                state=self.state)
        spans.pop("queue", _NULL).close(error=error)
        spans.pop("prefill", _NULL).annotate(
            chunks=self.chunks_done).close(error=error)
        spans.pop("decode", _NULL).annotate(
            tokens=len(self.tokens)).close(error=error)
        spans.pop("request", _NULL).annotate(
            tokens=len(self.tokens), state=self.state).close(error=error)

    def _close(self, now, outcome, error=None):
        self.finish_t = now
        if self.anatomy is not None and self.anatomy.owner == "engine":
            anatomy.complete(self.anatomy, now, outcome,
                             tokens=len(self.tokens))
        self._trace_close(error=error)
        self._stream.put(_DONE)
        self._done.set()

    def _finish(self, now):
        self.state = "done"
        self._close(now, "ok")

    def _fail(self, exc, now):
        from ..fault.retry import classify_exception

        self.state = "failed"
        self.error = exc
        self.error_class = classify_exception(exc)
        self._close(now, "expired" if isinstance(exc, DeadlineExceeded)
                    else "failed", error=exc)


class Scheduler:
    """Single-threaded scheduling core (the thread-safe wrapper is
    `api.ServeEngine`; everything here assumes the caller holds its
    lock)."""

    def __init__(self, slots: SlotDecoder, max_queue=128, policy="fifo",
                 default_deadline=None, eos_id=None, seed=0):
        if policy not in POLICIES:
            raise ValueError(
                f"unknown policy {policy!r} (valid: {', '.join(POLICIES)})")
        self.slots = slots
        self.max_queue = int(max_queue)
        self.policy = policy
        self.default_deadline = default_deadline
        self.eos_id = eos_id
        self._seed = seed
        self._base_key = None
        self._key_ctr = 0
        S = slots.max_slots
        # bounded by the max_queue check in submit (QueueFull is the
        # loud contract; deque(maxlen=) would silently drop instead)
        self._queue = collections.deque()   # noqa: FL011
        self._in_slot = [None] * S          # Request or None
        self._pos = onp.zeros(S, onp.int32)
        self._last = onp.zeros(S, onp.int32)
        self._active = onp.zeros(S, bool)   # DECODE-active (prefilled)
        self._temps = onp.ones(S, onp.float32)
        self._n_active = 0                  # occupied (prefilling+decoding)
        self._n_decoding = 0
        self._prefilled = []                # disagg: awaiting migration
        # the decode step launched and not yet fetched: its tokens (a
        # device array) and its rows ``[(slot, request)]``
        self._flight = None
        # the open dry interval ``[t0, cause]`` (t0 None: none open), shared
        # with each iteration's step clock (`StepClock.watch_dry`); a slots
        # object that computes on the host has no `device_dry` and no account
        self._dry = [None, None]
        self._device_dry = getattr(slots, "device_dry", None)
        self._next_id = 0
        self.closed = False
        # cost-ledger identity (the gateway stamps the model name) and
        # the previous step's clock for KV page-second accrual
        self.capacity_model = "serve"
        self._cap_last_t = None
        # speculative decoding: k drafted tokens verified per round
        # (0 = plain one-token decode; stub engines have no spec_k)
        self._spec_k = int(getattr(slots, "spec_k", 0) or 0)
        # the page arithmetic is the slots object's (`SlotDecoder.
        # pages_needed` / `pages_at`); a stand-in that has only
        # `page_tokens` maps every page by position
        by_position = lambda n: -(-int(n) // slots.page_tokens)  # noqa: E731
        self._pages_for = getattr(slots, "pages_needed", by_position)
        self._pages_at = getattr(slots, "pages_at", by_position)
        self._lazy_pages = bool(getattr(slots, "lazy_pages", False))

    # -- keys ---------------------------------------------------------------

    def _next_key(self):
        import jax

        if self._base_key is None:
            self._base_key = jax.random.PRNGKey(self._seed)
        self._key_ctr += 1
        return jax.random.fold_in(self._base_key, self._key_ctr)

    # -- admission ----------------------------------------------------------

    def _pages_needed(self, req):
        """Worst-case pool pages for the whole request: prompt plus every
        generated token that gets a KV write (the last sampled token is
        returned but never re-attended, so it needs no page).

        A ``prefill_only`` handoff segment (disagg) needs only the
        prompt's pages: its first token samples from logits without a KV
        write, and every decode write happens on the adopting replica —
        this is exactly why a dedicated prefill replica holds so few
        resident pages.

        The arithmetic is the slots object's (`pages_needed`): a family
        whose slots free pages as they go holds fewer than
        ``ceil(positions / page_tokens)``."""
        if req.prefill_only:
            return self._pages_for(req.prompt.size)
        return self._pages_for(self._written(req))

    @staticmethod
    def _written(req):
        """Positions of `req` that get a K/V row."""
        return req.prompt.size + req.max_new - 1

    def _pages_initial(self, req):
        """Pages physically allocated at admit. Non-spec: the full
        worst case (decode can never OOM mid-flight). Spec mode: just
        the prompt plus the first decode write position — the rest of
        the worst-case budget stays a *reservation* (`spec_reserved`)
        that speculative rounds draw down page-by-page and rejected
        suffixes roll back into, so admission pressure is identical to
        the non-spec engine (reserved pages are counted against
        admissions) but unaccepted lookahead never holds real pages. A
        family with `lazy_pages` (pages taken and freed as a slot goes)
        starts with its first chunk's and draws the same reservation down
        position by position (`_map_positions`)."""
        if self._lazy_pages:
            return self._pages_at(
                min(req.prompt.size, self.slots.prefill_chunk))
        if req.prefill_only or not self._spec_k:
            return self._pages_needed(req)
        return self._pages_at(req.prompt.size + 1)

    def _spec_reserved_total(self):
        """Pages budgeted to live requests but not physically held —
        the admission ledger that makes speculative page extension
        infallible: ``free_pages >= reserved_total`` is an invariant
        (asserted in tests), so drawing down a reservation never needs
        eviction and never deadlocks."""
        return sum(r.spec_reserved for r in self._in_slot if r is not None)

    def submit(self, prompt_ids, max_new_tokens, temperature=1.0,
               eos_id=None, deadline_s=None, parent_span=None,
               tenant=None, prefill_only=False, t_call=None):
        """`t_call`: the caller's stamp (the step clock's clock) from
        before it waited for the engine lock (`ServeEngine.submit`)."""
        if self.closed:
            raise EngineClosed("serve engine is shut down; new work is "
                               "rejected")
        prompt = onp.asarray(prompt_ids, onp.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        total = prompt.size + max_new
        if total > self.slots.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) "
                f"exceeds the engine's max_len ({self.slots.max_len})")
        if len(self._queue) >= self.max_queue:
            raise QueueFull(
                f"admission queue at capacity ({self.max_queue} waiting, "
                f"{self._n_active}/{self.slots.max_slots} slots busy) — "
                "shed load, raise max_queue, or retry with backoff")
        if deadline_s is None:
            deadline_s = self.default_deadline
        now = time.monotonic()
        req = Request(self._next_id, prompt, max_new,
                      float(temperature),
                      self.eos_id if eos_id is None else eos_id,
                      None if deadline_s is None else now + float(deadline_s),
                      parent_span=parent_span, tenant=tenant)
        req.prefill_only = bool(prefill_only)
        if req.prefill_only and self._lazy_pages:
            req._trace_close()
            raise NotImplementedError(
                "prefill-only handoff moves pages mapped by position; this "
                "engine's slots take and free pages as they go")
        if req.prefill_only and not getattr(self.slots, "page_handoff", True):
            req._trace_close()
            raise NotImplementedError(
                "prefill-only handoff is not served for this engine's "
                "family: its pages are not moved between engines")
        if self._pages_needed(req) > self.slots.allocator.usable_pages:
            req._trace_close()
            raise PagePoolExhausted(
                f"request needs {self._pages_needed(req)} KV pages but the "
                f"pool only has {self.slots.allocator.usable_pages} — "
                "raise n_pages or shrink prompt/max_new_tokens")
        self._next_id += 1
        req.submit_t = now
        req.t_enqueued = time.perf_counter()
        req.t_submit_call = req.t_enqueued if t_call is None else t_call
        SUBMIT_LOCK_WAIT.observe(req.t_enqueued - req.t_submit_call)
        self._queue.append(req)
        QUEUE_DEPTH.set(len(self._queue))
        return req

    def _sjf_key(self, req):
        """SJF under chunked prefill = fewest REMAINING prefill chunks
        first (a cached prefix makes a long prompt cheap), prompt length
        as the cold-cache tie-break, id for determinism."""
        shared = self.slots.prefix_cache.shared_tokens(req.prompt)
        remaining = max(1, int(req.prompt.size) - shared)
        chunks = -(-remaining // self.slots.prefill_chunk)
        return (chunks, int(req.prompt.size), req.id)

    def _next_index(self):
        if self.policy == "sjf":
            return min(range(len(self._queue)),
                       key=lambda j: self._sjf_key(self._queue[j]))
        return 0

    def _remove_at(self, i):
        self._queue.rotate(-i)
        req = self._queue.popleft()
        self._queue.rotate(i)
        return req

    # -- the step loop ------------------------------------------------------

    def step(self, waited_since=None):
        """One scheduling iteration (expire → admit → prefill chunks →
        launch the next decode step → fetch the last one → retire).
        Returns True if any progress was made.
        `waited_since`: the caller's stamp from before it waited for the
        lock it now holds (`ServeEngine.step`).

        The iteration's boundaries are stamped once each on a step clock
        (always on: a profiler span each, one step record, the phase
        counters); the armed ``serve.*`` spans and the capacity / anatomy
        ledgers take their times from those stamps."""
        from ..fault.injection import inject_at

        # host-side only — nothing here is ever traced by XLA, so the
        # zero-steady-state-recompile guarantee is untouched
        with tracing.StepClock(waited_since, queued=len(self._queue),
                               active=self._n_active) as clock:
            if self._device_dry is not None:
                # a launch site that finds the device dry: the host was
                # late, unless this iteration began with an empty engine
                clock.watch_dry(
                    self._dry, self._device_dry,
                    "no_work" if not self._n_active and self._flight is None
                    else "late_launch")
            with tracing.phase("mx.serve.admit", "admit"):
                inject_at("serve_step")
                now = time.monotonic()
                self._accrue_page_seconds(clock.t_start)
                self._expire(now)
                admitted = self._admit(now, clock.t_start)
            chunks = self._prefill_step(clock)
            decoded = self._decode_once(clock)
            with tracing.phase("mx.serve.emit", "emit"):
                self._refresh_gauges()
            if self._dry[0] is not None and not self._n_active \
                    and not self._queue:
                self._dry[1] = "no_work"    # whatever opened it: nothing to run
            clock.progressed = bool(admitted or chunks or decoded)
            # the decode launch counted its rows and pages on the clock,
            # the fetch its overshoot rows
            clock.counts = {
                "pages_live": 0, "pages_view": 0, "decoding": 0,
                "mode": None, "overshoot": 0, **clock.counts,
                "chunks": chunks,
                "prefilling": self._n_active - self._n_decoding,
                "queued": len(self._queue)}
        for ph, seconds in clock.seconds.items():
            if seconds:
                STEP_SECONDS[ph].inc(seconds)
        for t0, t1, cause in clock.dry:
            DEVICE_DRY[cause].inc(t1 - t0)
        return clock.progressed

    def _dry_opens(self, cause):
        """A blocking fetch just returned (the last boundary's stamp) and
        nothing was launched behind what it fetched: the device has nothing
        queued until the next launch returns (`StepClock.launched`)."""
        if self._device_dry is not None and self._dry[0] is None:
            self._dry[:] = tracing.stamp(), cause

    def _accrue_page_seconds(self, t):
        """Cost-ledger seam: resident KV pages × seconds since the last
        step's start `t`, per occupied slot (dead branch while the
        capacity ledger is disarmed — one module-attribute load per
        step)."""
        if not capacity._ENABLED:
            self._cap_last_t = None
            return
        last, self._cap_last_t = self._cap_last_t, t
        if last is None:
            return
        dt = t - last
        if dt <= 0:
            return
        for req in self._in_slot:
            if req is not None and req.pages:
                capacity.charge_kv_page_seconds(
                    req.tenant, self.capacity_model, len(req.pages) * dt)

    def _expire(self, now):
        for req in [r for r in self._queue
                    if r.deadline is not None and now > r.deadline]:
            self._queue.remove(req)
            req._fail(DeadlineExceeded(
                f"request {req.id} expired after "
                f"{now - req.submit_t:.3f}s in queue"), now)
        late = [(s, req) for s, req in enumerate(self._in_slot)
                if req is not None and req.deadline is not None
                and now > req.deadline]
        if late:
            self.settle()     # what the step in flight made is theirs
        for s, req in late:
            if self._in_slot[s] is req:       # not finished by that step
                self._retire(s, "deadline", now)

    def _admit(self, now, t_step):
        """Map queued requests onto free slots + reserved pages. The full
        page budget is taken up front so decode can never OOM mid-flight;
        when even the unused prefix-cache entries cannot cover it, the
        request stays queued until running slots retire. `t_step` is the
        iteration's start stamp (the request record's ``t_admit``)."""
        admitted = 0
        alloc = self.slots.allocator
        cache = self.slots.prefix_cache
        while self._queue and self._n_active < self.slots.max_slots:
            i = self._next_index()
            req = self._queue[i]
            shared_tokens, shared_pages = cache.lookup(req.prompt)
            # the admission budget is ALWAYS the worst case — under spec
            # decode the tail of it is reserved rather than allocated,
            # but reserved pages count against later admissions all the
            # same, so lazy allocation never over-admits
            need = self._pages_needed(req) - len(shared_pages)
            reserved = self._spec_reserved_total()
            if need > alloc.free_pages - reserved:
                cache.evict_unused(need + reserved)
            if need > alloc.free_pages - reserved:
                break                      # pages pinned by live requests
            self._remove_at(i)
            slot = self._in_slot.index(None)
            alloc.incref(shared_pages)
            grab = self._pages_initial(req) - len(shared_pages)
            req.pages = shared_pages + alloc.alloc(grab)
            req.spec_reserved = need - grab
            req.shared_tokens = shared_tokens
            req.prefill_pos = shared_tokens
            self.slots.set_slot_pages(slot, req.pages)
            req.state = "running"
            req.slot = slot
            self._in_slot[slot] = req
            self._n_active += 1
            admitted += 1
            req.t_admit = t_step
            QUEUE_WAIT.observe(req.t_admit - req.t_enqueued)
            if req.anatomy is not None and req.anatomy.owner == "engine":
                # standalone engine: admit IS dispatch (the gateway
                # stamps its own records at gateway dispatch instead)
                req.anatomy.dispatched(now, anatomy._sched_replica(self)[0])
            if shared_tokens:
                PREFIX_HITS.inc()
                PREFIX_TOKENS.inc(shared_tokens)
            root = req._spans.get("request", _NULL)
            req._spans.pop("queue", _NULL).close()   # admit ends the wait
            req._spans["prefill"] = tracing.open_span(
                "serve.prefill", parent=root, slot=slot,
                prompt_len=int(req.prompt.size),
                shared_tokens=shared_tokens)
        return admitted           # the step's end refreshes the gauges

    def _prefill_step(self, clock):
        """ONE chunk for every slot still prefilling — chunks of
        different requests and decode steps interleave, so a long prompt
        cannot stall the engine for its whole prefill. The final chunk
        emits the request's first token and flips the slot to decoding.
        A chunk is launched, and only a prompt's final chunk is waited
        for: the token an intermediate chunk samples is never fetched, so
        the host goes on to queue the next program behind it."""
        ran = 0
        for slot, req in enumerate(self._in_slot):
            if req is None or req.prefill_pos >= req.prompt.size:
                continue
            t0 = req.prefill_pos
            n = min(self.slots.prefill_chunk, req.prompt.size - t0)
            last_chunk = t0 + n == req.prompt.size
            if self._lazy_pages:
                self._map_positions(slot, req, t0, t0 + n)
            # the chunk's window, from the step clock's stamps: the
            # boundary before it to the end of its launch (the engine
            # stamps that inside) or, for a final chunk, of its read-back
            w0 = clock.cursor
            first, bucket, pad = self.slots.prefill_chunk_step(
                slot, req.prompt[t0:t0 + n], t0, self._next_key,
                req.temperature)
            if last_chunk:
                with tracing.phase("mx.serve.prefill.readback",
                                   "prefill_readback"):
                    first = self.slots.fetch_first(first)  # blocks: it ran
                w1 = clock.cursor
                self._dry_opens("chunk_fetch")    # the queue drained with it
            else:
                w1 = clock.lap("prefill_launch")
            PREFILL_FETCHES["final" if last_chunk else "skipped"].inc()
            # exact per-slot prefill attribution, one window shared by
            # the capacity + anatomy ledgers (dead branches while off)
            capacity.split_device_seconds(
                (req.tenant,), self.capacity_model, "prefill", w1 - w0)
            anatomy.on_prefill_chunk(self, req, w0, w1)
            req.prefill_pos = t0 + n
            req.chunks_done += 1
            PREFILL_CHUNKS.inc()
            ran += 1
            req._spans.get("prefill", _NULL).annotate(bucket=bucket,
                                                      pad_tokens=pad)
            if last_chunk:
                with tracing.phase("mx.serve.emit", "emit"):
                    self._prompt_done(slot, req, first)
        return ran

    def _prompt_done(self, slot, req, first):
        """The final chunk landed: the prompt's pages become shareable,
        the first token goes out, and the slot turns to decoding (or is
        retired or handed off at once)."""
        # whole prompt resident: its full pages become shareable
        self.slots.prefix_cache.register(req.prompt, req.pages)
        req._spans.pop("prefill", _NULL).annotate(
            chunks=req.chunks_done).close()
        t = time.monotonic()
        if req.anatomy is not None:
            req.anatomy.prefill_done(t, handoff=req.prefill_only)
        req._emit(first, t)
        if self._is_complete(req, first):
            # one-token request (or instant EOS): retired without
            # ever joining a decode step
            req._spans.get("request", _NULL).annotate(
                reason=self._complete_reason(req, first))
            self._retire(slot, self._complete_reason(req, first), t)
            return
        if req.prefill_only:
            # disagg handoff: the slot frees NOW but the page refs
            # ride with the request — the migration plane
            # (serve/disagg.py) claims it via take_prefilled() and
            # owns moving or releasing those pages
            req.first_token = int(first)
            self._handoff(slot, req)
            return
        self._pos[slot] = req.prompt.size
        self._last[slot] = first
        self._temps[slot] = max(req.temperature, 1e-6)
        self._active[slot] = True
        self._n_decoding += 1
        # the per-slot decode segment of the request's trace: opens
        # here, closes at retirement (tokens annotated at close)
        req._spans["decode"] = tracing.open_span(
            "serve.decode", parent=req._spans.get("request", _NULL),
            slot=slot)

    def _is_complete(self, req, tok):
        return (len(req.tokens) >= req.max_new
                or (req.eos_id is not None and tok == req.eos_id))

    def _complete_reason(self, req, tok):
        if req.eos_id is not None and tok == req.eos_id:
            return "eos"
        return "length"

    def _decode_once(self, clock):
        """Launch the next decode step, then fetch the one launched an
        iteration ago and hand its tokens out: the host's work for a
        launch lies under the device's work on the step before."""
        if self._spec_k:
            # the draft needs the verified tokens on the host: its round
            # fetches what it launches
            if not self._n_decoding:
                return False
            clock.counts["decoding"] = self._n_decoding
            return self._spec_decode_once(clock)
        landing = self._flight
        if not self._n_decoding and landing is None:
            return False
        # the window of the iteration's decode work, from the step
        # clock's stamps: the launch (the engine stamps it inside), then
        # the blocking fetch
        w0 = clock.cursor
        launched = None
        if self._n_decoding:
            clock.counts["mode"] = self._launch()
            # `_launch`'s tail (the rows' bookkeeping, slots freed in
            # advance) is the launch's, and of its parts the bookkeeping
            # one: the read-back that follows is the blocking fetch alone
            clock.lap("decode_launch", "launch_prepare")
            launched = self._flight
            if landing is None and isinstance(launched[0], onp.ndarray):
                # a slots object that computes on the host: its tokens
                # are here already, nothing is in flight
                landing = launched
        w1 = clock.cursor if landing is None else self._land(landing)
        self._decode_window(w0, w1, (landing or launched)[1])
        return True

    def _decode_window(self, w0, w1, rows):
        """The armed span and the ledgers' charge for one window of decode
        work (a launch, a fetch, or a launch and the fetch after it)."""
        tracing.record_span("serve.decode_step", w0, w1, slots=len(rows))
        self._charge_decode(w0, w1, rows)

    def _launch(self):
        """Queue one decode program over the decoding slots, behind the
        step in flight where there is one (mode ``"ahead"``; else
        ``"cold"``: the device waits while the host launches). A slot that
        goes on from the step in flight takes its last token from that
        step's output on the device (a negative entry of ``last_tok``,
        `SlotDecoder.decode_step`); one that turned to decoding since, or
        was adopted, takes the host's. What the host knows in advance it
        acts on now: ``pos`` rises with the launch, and a request whose
        token on its way is its ``max_new``-th leaves its slot at once
        (no row, no write and no key are spent on a step it cannot use;
        the programs that may touch its pages again are queued behind
        this one). Returns the mode. What follows `decode_step` here (the
        rows' bookkeeping) is charged to ``decode_launch`` by the caller's
        boundary, and of the launch's four parts (`tracing.LAUNCH_PARTS`)
        to ``launch_prepare``, which holds the host's bookkeeping on both
        sides of the call."""
        ahead = self._flight
        if self._lazy_pages:
            for s, req in enumerate(self._in_slot):
                if req is not None and self._active[s]:
                    pos = int(self._pos[s])
                    if self._pages_at(pos + 1) > len(req.pages) \
                            or self.slots.rolls_before(pos):
                        self._map_positions(s, req, pos, min(
                            self.slots.window_end(pos), self._written(req)))
        last = self._last
        if ahead is not None:
            last = last.copy()
            for s, req in ahead[1]:
                if req.slot == s:             # goes on from that step
                    last[s] = -1
        rows = self._decoding_rows()
        tokens = self.slots.decode_step(last, self._pos, self._active,
                                        self._next_key, self._temps)
        mode = "cold" if ahead is None else "ahead"
        DECODE_LAUNCHES[mode].inc()
        tracing.count(decoding=len(rows))
        for s, req in rows:
            req.inflight += 1
            self._pos[s] += 1
            if len(req.tokens) + req.inflight >= req.max_new:
                self._release(s, "length")
        self._flight = (tokens, rows)
        return mode

    def _land(self, flight):
        """Fetch a launched decode step's tokens (the one host sync of a
        step) and hand them out; retire what an EOS ends, and close what
        `_launch` freed in advance. A row whose request has ended since it
        was launched (an EOS learnt a step late) is an overshoot: its
        token is dropped, its write went to the request's own page or the
        trash page. Returns the stamp at which the fetch ended."""
        if flight is self._flight:
            self._flight = None
        tokens, rows = flight
        with tracing.phase("mx.serve.decode.readback", "decode_readback"):
            tokens = self.slots.fetch_tokens(tokens)  # blocks: it ran
        fetched = tracing.stamp()
        if self._flight is None:
            self._dry_opens("cold_fetch")     # nothing launched behind it
        with tracing.phase("mx.serve.emit", "emit"):
            now = time.monotonic()
            for s, req in rows:
                req.inflight -= 1
                if req.state != "running":
                    OVERSHOOT_ROWS.inc()
                    tracing.count(overshoot=1)
                    continue
                tok = int(tokens[s])
                req._emit(tok, now)
                if not self._is_complete(req, tok):
                    if req.slot is not None:
                        self._last[s] = tok
                elif req.slot is None:        # `_launch` freed its slot
                    self._close_request(
                        req, self._complete_reason(req, tok), now)
                else:
                    self._retire(s, self._complete_reason(req, tok), now)
        return fetched

    def settle(self):
        """Fetch the decode step in flight, if there is one, and hand its
        tokens out. Whatever changes a slot outside an iteration's own
        order calls this first: afterwards ``pos``, ``last`` and every
        request's tokens say the same thing."""
        flight = self._flight
        if flight is not None:
            w0 = tracing.stamp()
            self._decode_window(w0, self._land(flight), flight[1])

    def _charge_decode(self, w0, w1, rows=None):
        """ONE batched program (or draft + verify round) per step: split
        its window evenly across the requests of its `rows` (default: the
        slots decoding now) (capacity) and charge the replica's decode
        residency (anatomy). Dead branches while both are off. Returns
        the anatomy's delta."""
        if capacity._ENABLED:
            if rows is None:
                rows = self._decoding_rows()
            capacity.split_device_seconds(
                [req.tenant for _, req in rows],
                self.capacity_model, "decode", w1 - w0)
        return anatomy.on_decode_step(self, w0, w1)

    def _decoding_rows(self):
        return [(s, req) for s, req in enumerate(self._in_slot)
                if req is not None and self._active[s]]

    def _map_positions(self, slot, req, pos, end):
        """A slot with `lazy_pages`, before it writes rows at positions
        ``pos .. end - 1`` (all in one window): where `pos` opens a window,
        the window before it is rolled into summary pages and its pages go
        back to the allocator; then the reservation is drawn down until the
        positions are mapped. ``len(req.pages) + req.spec_reserved`` stays
        the admission-time worst case (`_extend_spec_pages`), which
        `slots.pages_needed` sized for the roll's moment: `alloc` cannot
        fail here."""
        slots, alloc = self.slots, self.slots.allocator
        pages = list(req.pages)
        if slots.rolls_before(pos):
            n_win = slots.window_pages
            new = alloc.alloc(slots.summary_pages)
            slots.roll_step(slot, pages[-n_win:], new)
            alloc.decref(pages[-n_win:])
            pages = pages[:-n_win] + new
        pages += alloc.alloc(max(0, slots.pages_at(end) - len(pages)))
        req.spec_reserved += len(req.pages) - len(pages)
        req.pages = pages
        slots.set_slot_pages(slot, pages)

    def _extend_spec_pages(self, slot, req, k):
        """Draw down `req`'s reservation so every position the verify
        program may write this round (up to ``pos + k``, capped at the
        last position the request can ever write) is page-mapped.

        ``len(req.pages) + req.spec_reserved`` equals the admission-time
        worst case throughout a request's life (extension and trim move
        pages between the two sides), so ``grab`` never exceeds the
        reservation and — with ``free_pages >= reserved_total`` held by
        admission — ``alloc`` never fails here. No eviction, no
        deadlock, no mid-flight OOM.

        Returns the last page-mapped position (the verify program's
        per-slot write ``limit``)."""
        pt = self.slots.page_tokens
        pos = int(self._pos[slot])
        # largest position ever written: the final emitted token's KV is
        # never stored, so the write range ends at prompt + max_new - 2
        res_end = req.prompt.size + req.max_new - 2
        target = min(pos + k, res_end)
        want = target // pt + 1
        grab = min(want - len(req.pages), req.spec_reserved)
        if grab > 0:
            req.pages = list(req.pages) + self.slots.allocator.alloc(grab)
            req.spec_reserved -= grab
            self.slots.set_slot_pages(slot, req.pages)
        return len(req.pages) * pt - 1

    def _trim_spec_pages(self, slot, req):
        """Rollback after acceptance: pages mapped for rejected draft
        positions go back to the free list and re-credit the request's
        reservation. Trimmed pages are always this round's speculative
        extension (``keep`` covers at least the whole prompt plus one
        emitted token), never shared-prefix pages, so their refcount is
        1 and the decref frees them immediately."""
        keep = int(self._pos[slot]) // self.slots.page_tokens + 1
        if len(req.pages) > keep:
            extra = req.pages[keep:]
            req.pages = list(req.pages[:keep])
            self.slots.allocator.decref(extra)
            req.spec_reserved += len(extra)
            self.slots.set_slot_pages(slot, req.pages)

    def _spec_decode_once(self, clock):
        """One speculative round: map pages for the verify window, draft
        ``k`` tokens (host n-gram or one draft-model program), verify
        all ``k + 1`` rows in ONE batched target program, then accept
        the longest matching prefix per slot on the host. Every emitted
        token is the target model's own greedy choice, so output is
        token-for-token identical to non-speculative greedy decode."""
        k = self._spec_k
        # the round's window from the step clock's stamps: draft, then
        # verify; both are the step's decode work (host drafting and page
        # mapping count as launch, the engine stamps its read-backs)
        w0 = clock.cursor
        limit = onp.zeros(self.slots.max_slots, onp.int32)
        for s, req in enumerate(self._in_slot):
            if req is not None and self._active[s]:
                limit[s] = self._extend_spec_pages(s, req, k)
        if self.slots.draft_kind == "model":
            drafts = self.slots.spec_draft_step(
                self._last, self._pos, self._active, limit)
        else:
            seqs = [None] * self.slots.max_slots
            for s, req in enumerate(self._in_slot):
                if req is not None and self._active[s]:
                    seqs[s] = onp.concatenate(
                        [req.prompt, onp.asarray(req.tokens, onp.int32)])
            drafts = self.slots.spec_propose(seqs)
        w_mid = clock.lap("decode_launch")
        tracing.record_span("serve.spec_draft", w0, w_mid,
                            slots=self._n_decoding, k=k)
        targets = self.slots.spec_verify_step(
            self._last, drafts, self._pos, self._active, limit)
        w1 = clock.lap("decode_readback")
        self._dry_opens("cold_fetch")     # the round fetched what it launched
        tracing.record_span("serve.spec_verify", w_mid, w1,
                            slots=self._n_decoding, k=k)
        spec_dt = self._charge_decode(w0, w1)
        with tracing.phase("mx.serve.emit", "emit"):
            self._spec_accept(k, drafts, targets, limit, spec_dt)
        return True

    def _spec_accept(self, k, drafts, targets, limit, spec_dt):
        """Accept the longest matching drafted prefix per slot, emit,
        retire or roll the lookahead pages back."""
        n_round = max(self._n_decoding, 1)
        now = time.monotonic()
        for s, req in enumerate(self._in_slot):
            if req is None or not self._active[s]:
                continue
            pos = int(self._pos[s])
            # rows past the page-mapped window wrote to the trash page;
            # their targets are unusable as continuation context
            room = int(limit[s]) - pos
            m = 0
            while (m < k and m < room
                   and int(drafts[s, m]) == int(targets[s, m])):
                m += 1
            # full-accept rounds with a model draft forgo the bonus
            # token: the draft program never wrote KV for its own last
            # draft (it writes positions pos..pos+k-1), so advancing
            # past it would leave a hole in the draft pool and poison
            # every later draft round. Row k's KV in the TARGET pool is
            # written by the verify program either way.
            bonus = 0 if (m == k
                          and self.slots.draft_kind == "model") else 1
            emitted = 0
            done_reason = None
            for i in range(min(m + bonus, req.max_new - len(req.tokens))):
                tok = int(targets[s, i])
                req._emit(tok, now)
                emitted += 1
                if self._is_complete(req, tok):
                    done_reason = self._complete_reason(req, tok)
                    break
            accepted = min(m, emitted)
            if spec_dt and req.anatomy is not None:
                # the rejected fraction of this slot's share of the
                # round is speculation waste; the rest stays ambient
                # decode_compute
                req.anatomy.carve(
                    "spec_overhead",
                    spec_dt * (k - accepted) / max(k, 1) / n_round)
            SPEC_DRAFTED.inc(k)
            SPEC_ACCEPTED.inc(accepted)
            self.slots.spec_count(k, accepted)
            self._pos[s] = pos + emitted
            self._last[s] = req.tokens[-1]
            if done_reason is not None:
                self._retire(s, done_reason, now)
            else:
                self._trim_spec_pages(s, req)

    def _retire(self, slot, reason, now):
        """Free `slot` and dereference its pages (shared pages survive
        via their cache/peer references). EOS/length retirement completes
        the request; deadline/shutdown fails it."""
        self._close_request(self._release(slot, reason), reason, now)

    def _release(self, slot, reason):
        """Free `slot` for the next admission and dereference its pages;
        returns the request that held it, now in no slot and not closed."""
        req = self._in_slot[slot]
        self._in_slot[slot] = None
        if self._active[slot]:
            self._n_decoding -= 1
        self._active[slot] = False
        self._pos[slot] = 0
        self._last[slot] = 0
        self._temps[slot] = 1.0
        self._n_active -= 1
        if req.pages:
            self.slots.allocator.decref(req.pages)
        req.pages = None
        req.spec_reserved = 0
        self.slots.clear_slot(slot)
        req.slot = None
        EVICTIONS.inc()
        registry.counter(
            "mx_serve_evictions_total",
            "slots freed (EOS / length / deadline / shutdown / "
            "preemption)",
            labels={"reason": reason}).inc()
        return req

    def _close_request(self, req, reason, now):
        """The end of a request that is in no slot any more: EOS/length
        completes it, deadline/shutdown fails it."""
        req._spans.get("request", _NULL).annotate(reason=reason)
        if reason == "deadline":
            req._fail(DeadlineExceeded(
                f"request {req.id} expired mid-flight after "
                f"{len(req.tokens)}/{req.max_new} tokens"), now)
        elif reason == "shutdown":
            req._fail(EngineClosed(
                f"engine shut down with request {req.id} mid-flight "
                f"({len(req.tokens)}/{req.max_new} tokens)"), now)
        else:
            req._finish(now)

    def preempt(self, slot, now=None):
        """Evict the request in `slot` WITHOUT finishing or failing it —
        the gateway's priority-preemption seam.

        The page-aligned part of the work already resident (prefilled
        prompt pages plus decoded-token pages) is registered in the
        prefix cache before the slot's references drop, so the cache
        keeps those pages alive where the page budget allows: a resumed
        submission of ``prompt + tokens-so-far`` re-attaches them as a
        shared prefix and only re-prefills the unaligned tail — warm
        resume instead of a cold restart. Under page pressure the cached
        pages are reclaimable through the normal ``evict_unused`` path
        (preempted work never pins pages against higher-priority
        admissions).

        Returns the `Request` with ``state="preempted"`` (its trace is
        closed; the caller owns re-queueing the remaining work)."""
        refusal = getattr(self.slots, "preempt_refusal", None)
        if refusal:
            raise NotImplementedError(refusal)
        if now is None:
            now = time.monotonic()
        # the tokens of the step in flight are the request's (one of them
        # may be its last: then the slot is free and nothing is preempted)
        self.settle()
        req = self._in_slot[slot]
        if req is None:
            raise ValueError(f"slot {slot} is empty — nothing to preempt")
        pt = self.slots.page_tokens
        # tokens with KV resident in the pool: the whole prefilled range
        # for a prefilling slot, pos (prompt + all but the newest token)
        # for a decoding one
        resident = int(self._pos[slot]) if self._active[slot] \
            else int(req.prefill_pos)
        aligned = (resident // pt) * pt
        if aligned and req.pages:
            gen = req.tokens[:max(0, resident - req.prompt.size)]
            seq = onp.concatenate(
                [req.prompt, onp.asarray(gen, onp.int32)])[:aligned]
            self.slots.prefix_cache.register(seq, req.pages[:aligned // pt])
        self._release(slot, "preempted")
        req.state = "preempted"
        req._spans.get("request", _NULL).annotate(
            reason="preempted", resident_tokens=resident,
            kept_pages=aligned // pt)
        req._trace_close()
        return req

    # -- disaggregation (prefill/decode handoff; serve/disagg.py) -----------

    def _handoff(self, slot, req):
        """Free `slot` WITHOUT dereferencing the request's pages: the
        prompt's KV stays resident (prefix-cache refs plus the request's
        own) while ownership of the request moves to the migration
        plane. The slot is immediately reusable for the next prompt —
        a prefill replica's slots turn over per-prompt, never per-token."""
        self._in_slot[slot] = None
        self._n_active -= 1
        self.slots.clear_slot(slot)
        req.slot = None
        req.state = "prefilled"
        self._prefilled.append(req)

    def take_prefilled(self):
        """Claim the requests whose prefill-only segment completed since
        the last call. The caller (the disagg migration plane) inherits
        their page references — it must migrate, adopt, or release each
        one; nothing else will."""
        out, self._prefilled = self._prefilled, []
        return out

    @property
    def prefill_backlog(self):
        """Prefill chunks still owed: queued prompts plus the un-run
        remainder of every prefilling slot — the router's placement key
        for prefill-role replicas (decode replicas always score 0)."""
        pc = self.slots.prefill_chunk
        n = 0
        for r in self._queue:
            n += -(-max(1, int(r.prompt.size)) // pc)
        for r in self._in_slot:
            if r is not None and r.prefill_pos < r.prompt.size:
                n += -(-(int(r.prompt.size) - r.prefill_pos) // pc)
        return n

    def adopt_page_plan(self, prompt_len, max_new):
        """Page budget for adopting a prefilled request: ``(content,
        physical, reserved)`` — `content` pages carry migrated prompt
        KV, `physical` is what the caller must hand to :meth:`adopt`
        (under spec decode the tail stays a reservation, mirroring
        `_pages_initial`), and `reserved` seeds ``spec_reserved``."""
        if self._lazy_pages:
            raise NotImplementedError(
                "adoption hands over pages mapped by position; this "
                "engine's slots take and free pages as they go")
        if not getattr(self.slots, "page_handoff", True):
            raise NotImplementedError(
                "adoption is not served for this engine's family: its pages "
                "are not moved between engines")
        pt = self.slots.page_tokens
        content = -(-prompt_len // pt)
        total = -(-(prompt_len + max_new - 1) // pt)
        if self._spec_k:
            physical = max(content, -(-(prompt_len + 1) // pt))
        else:
            physical = total
        return content, physical, total - physical

    def adopt(self, prompt_ids, first_token, max_new_tokens, pages,
              spec_reserved=0, temperature=1.0, eos_id=None,
              deadline_s=None, parent_span=None, tenant=None):
        """Admit a request whose prefill ALREADY happened elsewhere: the
        decode half of the disagg handoff. `pages` (ownership transfers
        — no incref here) must already hold the prompt's KV in THIS
        engine's pool per :meth:`adopt_page_plan`; `first_token` is the
        token the prefill side sampled. The request enters directly in
        the decoding state, so this replica's compile ledger never sees
        a prefill family — the disaggregation invariant.

        The adopted request is page-for-page and position-for-position
        identical to one that prefilled here (first token seeded as
        ``tokens[0]``, pos at the prompt boundary), so greedy decode is
        bit-identical to co-located serving."""
        if self.closed:
            raise EngineClosed("serve engine is shut down; new work is "
                               "rejected")
        if self._n_active >= self.slots.max_slots:
            raise QueueFull(
                f"no free slot to adopt into "
                f"({self._n_active}/{self.slots.max_slots} busy) — the "
                "migration plane must pick a viable decode replica")
        prompt = onp.asarray(prompt_ids, onp.int32).reshape(-1)
        max_new = int(max_new_tokens)
        now = time.monotonic()
        if deadline_s is None:
            deadline_s = self.default_deadline
        req = Request(self._next_id, prompt, max_new,
                      float(temperature),
                      self.eos_id if eos_id is None else eos_id,
                      None if deadline_s is None else now + float(deadline_s),
                      parent_span=parent_span, tenant=tenant)
        self._next_id += 1
        req.submit_t = now
        # the first token was sampled (and its TTFT observed) on the
        # prefill side — seed it so completion math and spec-page math
        # line up with the co-located request, without re-emitting it
        req.first_token_t = now
        req.first_token = int(first_token)
        req.tokens.append(int(first_token))
        req.prefill_pos = prompt.size
        req.shared_tokens = 0
        slot = self._in_slot.index(None)
        req.pages = list(pages)
        req.spec_reserved = int(spec_reserved)
        self.slots.set_slot_pages(slot, req.pages)
        req.state = "running"
        req.slot = slot
        self._in_slot[slot] = req
        self._n_active += 1
        self._pos[slot] = prompt.size
        self._last[slot] = int(first_token)
        self._temps[slot] = max(float(temperature), 1e-6)
        self._active[slot] = True
        self._n_decoding += 1
        root = req._spans.get("request", _NULL)
        root.annotate(adopted=True)
        req._spans.pop("queue", _NULL).close()
        req._spans["decode"] = tracing.open_span(
            "serve.decode", parent=root, slot=slot)
        return req

    def finish_handoff(self, req):
        """Close out a prefilled segment whose work moved elsewhere
        (migrated or re-queued). Pages must already be handed off or
        dereferenced by the caller — this only settles trace state."""
        req.state = "migrated"
        req._trace_close()

    @property
    def free_slots(self):
        return self.slots.max_slots - self._n_active

    def _refresh_gauges(self):
        QUEUE_DEPTH.set(len(self._queue))
        SLOT_OCCUPANCY.set(self._n_active / self.slots.max_slots)

    # -- lifecycle ----------------------------------------------------------

    @property
    def queue_depth(self):
        return len(self._queue)

    @property
    def n_active(self):
        """Requests the engine still owes tokens: the occupied slots, and
        the requests whose slot was freed when their last step was
        launched and whose last token is still in flight."""
        n = self._n_active
        if self._flight is not None:
            n += sum(1 for _, req in self._flight[1]
                     if req.slot is None and req.state == "running")
        return n

    @property
    def idle(self):
        """Nothing queued, no slot occupied, no step in flight."""
        return self._n_active == 0 and not self._queue \
            and self._flight is None

    def close(self, drain):
        """Stop admission. With ``drain`` the caller keeps stepping until
        occupied slots finish (prefilling ones complete their remaining
        chunks first); queued (never-started) requests are failed either
        way — "finish running slots, reject new work"."""
        self.closed = True
        self.settle()         # what the step in flight made is delivered
        now = time.monotonic()
        while self._queue:
            req = self._queue.popleft()
            req._fail(EngineClosed(
                f"engine shut down before request {req.id} was "
                "admitted"), now)
        # prefilled handoff segments awaiting migration can't finish on
        # a closed engine: release their pages and fail them loudly (the
        # window is one gateway step, but shutdown must not leak pages)
        for req in self._prefilled:
            if req.pages:
                self.slots.allocator.decref(req.pages)
            req.pages = None
            req._fail(EngineClosed(
                f"engine shut down with request {req.id} awaiting "
                "migration"), now)
        self._prefilled = []
        if not drain:
            for s, req in enumerate(self._in_slot):
                if req is not None:
                    self._retire(s, "shutdown", now)
        self._refresh_gauges()

    def abandon(self):
        """Crash teardown: the engine under this scheduler is DEAD (a
        replica crash) and every in-flight request has already been
        re-owned by the caller (`serve/elastic.py` detaches the gateway
        handles and re-queues the work on a surviving replica). Unlike
        `close`, nothing is failed — the Requests still here are
        orphaned engine segments whose tenants have moved on; queue and
        slots are dropped as-is so the page pool can be released. The step
        in flight is dropped unfetched with them: its tokens were handed
        to nobody, so the re-queued work makes them again, once."""
        self.closed = True
        self._flight = None
        self._queue.clear()
        self._in_slot = [None] * self.slots.max_slots
        self._prefilled = []
        self._n_active = 0
        self._refresh_gauges()
