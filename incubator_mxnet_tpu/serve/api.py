"""`mx.serve.ServeEngine` — the thread-safe front door of the serving
subsystem.

Three ways in, one engine:

- ``generate(prompt_ids, max_new_tokens)`` — blocking; returns the full
  sequence (prompt + generated) as int32 numpy, same surface as
  `GPTDecoder.generate` for one request;
- ``submit(...)`` → handle + ``iter_tokens(handle)`` — streaming; tokens
  yield as each decode step lands them;
- ``generate_many([...])`` — batch convenience over submit+drive.

Threading model: ONE lock guards the scheduler; `step()` takes it for a
whole iteration, `submit()` only for admission. A background driver
(``start()``) can own the step loop while client threads submit and
stream — or, with no driver, whichever thread is blocked on a result
drives the engine itself (the lock makes concurrent drivers safe, just
redundant). ``shutdown(drain=True)`` stops admission, finishes the
requests already in slots, and fails the never-admitted queue — loudly.
"""
from __future__ import annotations

import queue as _queue
import threading
import time

import numpy as onp

from ..telemetry import anatomy, tracing
from ..telemetry.locks import tracked_lock
from ..util import env_float as _env_float
from ..util import env_int as _env_int
from .engine import SlotDecoder
from .scheduler import (STEP_SECONDS, EngineClosed, Request, Scheduler,
                        _DONE)

__all__ = ["ServeEngine"]

_IDLE_SLEEP_S = 0.002     # driver backoff when there is nothing to do
_DRIVER_MAX_CONSECUTIVE_FAILURES = 3


def slots_class(source):
    """The slots class that serves `source`, by its ``family`` (a source
    that names none is a GPT Block or `GPTDecoder`)."""
    from .eva import EvaSlotDecoder
    from .mla import MLASlotDecoder
    from .ssm import HybridSlotDecoder

    families = {None: SlotDecoder, "evabyte": EvaSlotDecoder,
                "pangu_moe": MLASlotDecoder, "nemotron_h": HybridSlotDecoder}
    family = getattr(source, "family", None)
    if family not in families:
        raise ValueError(
            f"unknown decoder family {family!r}: mx.serve has slots for "
            f"{sorted(f for f in families if f)} and, for a source that "
            "names none, the GPT block")
    return families[family]


class ServeEngine:
    """Continuous-batching inference engine over a decoder: a GPT Block
    (or a prebuilt `GPTDecoder`), or another family's decoder object.

    Parameters
    ----------
    block_or_decoder : Block | GPTDecoder | EvaByteDecoder | PanguDecoder
            | NemotronHDecoder
        The model to serve. Every family runs the same prefill-chunk and
        decode programs (`serve.engine.SlotDecoder`) over its own block,
        ``decoder.layer(li, lp, x, pos, cache)``, where
        ``cache.attend(li, q, k, v)`` writes the rows into the page pool
        and returns the attention output (`serve/pages.py`). A family
        brings its decoder (``embed`` / ``layer_params`` / ``layer`` /
        ``next_logits`` / ``kv_geometry`` / ``layer_kinds``) and a slots
        subclass with its
        page arithmetic and chunk cache: an `EvaByteDecoder` (``family =
        "evabyte"``) is served by `serve.eva.EvaSlotDecoder` (window and
        summary pages; no speculative decoding, int8 pages or prefix
        reuse), a `PanguDecoder` (``"pangu_moe"``) by
        `serve.mla.MLASlotDecoder` (latent pages; no speculative decoding,
        int8 pages or page handoff), a `NemotronHDecoder`
        (``"nemotron_h"``) by `serve.ssm.HybridSlotDecoder` (recurrent state
        per slot beside pages; no prefix reuse, speculative decoding, int8
        pages, page handoff or preemption). The table is `slots_class`'s; a
        ``family`` it does not name is an error.
    max_slots : int
        In-flight request capacity (static decode batch width).
    max_len : int, optional
        Per-slot sequence capacity; defaults to the model's position
        table length.
    page_tokens / prefill_chunk / n_pages / kv_dtype / prefix_reuse
        Paged-KV knobs, forwarded to `SlotDecoder` (defaults ride
        ``MXNET_SERVE_PAGE_TOKENS`` / ``MXNET_SERVE_PREFILL_CHUNK`` /
        ``MXNET_SERVE_KV_DTYPE``; see SERVING.md).
    policy : "fifo" | "sjf", optional
        Admission order (default ``MXNET_SERVE_POLICY`` or fifo).
    max_queue : int, optional
        Bounded admission queue depth (default ``MXNET_SERVE_MAX_QUEUE``
        or 128); a full queue raises `QueueFull` at submit.
    deadline_s : float, optional
        Default per-request deadline (``MXNET_SERVE_DEADLINE_S``;
        unset = none). Individual submits may override.
    eos_id : int, optional
        Token id that retires a request early (engine default;
        per-request override at submit).
    do_sample / top_k : static sampling mode (compiled in — per-request
        variation would recompile); `temperature` stays per-request.
    seed : int
        Base PRNG seed for sampled decode (greedy ignores it).
    spec_k : int, optional
        Speculative-decoding draft length (default
        ``MXNET_SERVE_SPEC_K`` or 0 = off). Requires greedy decoding;
        output stays token-for-token identical to ``spec_k=0``.
    draft : str | Block | GPTDecoder, optional
        Draft source when ``spec_k > 0``: ``"ngram"`` (host n-gram
        proposer, no extra device programs — the default, also via
        ``MXNET_SERVE_SPEC_DRAFT``) or a small model that shares the
        target's tokenizer/vocab.
    """

    def __init__(self, block_or_decoder, max_slots=8, max_len=None,
                 page_tokens=None, prefill_chunk=None, n_pages=None,
                 kv_dtype=None, prefix_reuse=None, policy=None,
                 max_queue=None, deadline_s=None, eos_id=None,
                 do_sample=False, top_k=None, temperature=1.0, seed=0,
                 spec_k=None, draft=None):
        import os

        slots = slots_class(block_or_decoder)(
                       block_or_decoder, max_slots=max_slots,
                       max_len=max_len, page_tokens=page_tokens,
                       prefill_chunk=prefill_chunk, n_pages=n_pages,
                       kv_dtype=kv_dtype, prefix_reuse=prefix_reuse,
                       do_sample=do_sample, top_k=top_k,
                       spec_k=spec_k, draft=draft)
        if policy is None:
            policy = os.environ.get("MXNET_SERVE_POLICY", "fifo")
        if max_queue is None:
            max_queue = _env_int("MXNET_SERVE_MAX_QUEUE", 128)
        if deadline_s is None:
            deadline_s = _env_float("MXNET_SERVE_DEADLINE_S", None)
        self._sched = Scheduler(slots, max_queue=max_queue, policy=policy,
                                default_deadline=deadline_s, eos_id=eos_id,
                                seed=seed)
        self._default_temperature = float(temperature)
        self._lock = tracked_lock("serve.engine")
        self._driver = None
        self._stop = threading.Event()

    # -- introspection ------------------------------------------------------

    @property
    def max_slots(self):
        return self._sched.slots.max_slots

    @property
    def max_len(self):
        return self._sched.slots.max_len

    @property
    def queue_depth(self):
        with self._lock:
            return self._sched.queue_depth

    @property
    def n_active(self):
        with self._lock:
            return self._sched.n_active

    @property
    def closed(self):
        return self._sched.closed

    @property
    def page_occupancy(self):
        """Fraction of usable KV pool pages referenced (shared pages
        counted once)."""
        a = self._sched.slots.allocator
        return a.used_pages / a.usable_pages if a.usable_pages else 0.0

    @property
    def kv_bytes_per_slot(self):
        """Resident KV pool bytes per decode slot (0 before first use)."""
        return self._sched.slots.kv_bytes_per_slot

    def spec_stats(self):
        """Speculative-decoding counters: ``{"k", "draft", "drafted",
        "accepted", "accept_rate"}`` (all zero when ``spec_k=0``)."""
        return self._sched.slots.spec_stats()

    def xla_program_count(self):
        """Compiled XLA programs currently live (prefill buckets + the
        one decode program) — constant in steady state."""
        return self._sched.slots.xla_program_count()

    # -- submission ---------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens, temperature=None,
               eos_id=None, deadline_s=None):
        """Enqueue one request; returns its handle (a `Request`).

        Raises `QueueFull` when the admission queue is at capacity and
        `EngineClosed` after shutdown — backpressure is the caller's
        signal, never a silent drop."""
        if temperature is None:
            temperature = self._default_temperature
        t_call = time.perf_counter()
        with tracing.phase("mx.serve.submit"), self._lock:
            req = self._sched.submit(prompt_ids, max_new_tokens,
                                     temperature=temperature,
                                     eos_id=eos_id, deadline_s=deadline_s,
                                     t_call=t_call)
            # standalone-engine anatomy: request == segment here, so the
            # engine owns the record end to end (the gateway attaches
            # its own records to segments AFTER its dispatch instead)
            rec = anatomy.begin(
                req.id, req.tenant, self._sched.capacity_model,
                "normal", req.submit_t, deadline=req.deadline)
            if rec is not None:
                rec.owner = "engine"
                req.anatomy = rec
            return req

    # -- driving ------------------------------------------------------------

    def step(self):
        """One scheduling iteration (admit + one decode step for every
        occupied slot). Returns True if progress was made.

        A crash (including an injected ``serve_step`` fault) leaves a
        flight-recorder dump behind — the postmortem carries the active
        requests' spans — and then propagates unchanged."""
        try:
            waited_since = time.perf_counter()
            with tracing.phase("mx.serve.lock_wait"):
                # the wait `with self._lock` makes, apart so that it
                # alone is the span
                self._lock.acquire()   # noqa: FL011
            try:
                return self._sched.step(waited_since)
            finally:
                self._lock.release()
        except Exception as e:
            from ..telemetry import hbm

            # RESOURCE_EXHAUSTED gets the OOM post-mortem (census +
            # compile ledger in the dump context); the generic dump is
            # skipped when the post-mortem already wrote one
            if hbm.maybe_oom_postmortem("serve_step", e) is None:
                tracing.maybe_flight_dump("serve_step", e)
            raise

    def _driver_running(self):
        d = self._driver
        return d is not None and d.is_alive()

    def _drive_until(self, reqs, timeout=None):
        """Make `reqs` finish: wait on the driver if one is running,
        otherwise step the engine from this thread."""
        t_end = None if timeout is None else time.monotonic() + timeout
        for req in reqs:
            while not req.done:
                if t_end is not None and time.monotonic() > t_end:
                    raise TimeoutError(
                        f"request {req.id} still {req.state} after "
                        f"{timeout}s")
                if self._driver_running():
                    req.wait(0.05)
                else:
                    progressed = self.step()
                    if not progressed and not req.done:
                        raise RuntimeError(
                            f"serve engine stalled: request {req.id} is "
                            f"{req.state} but the scheduler is idle "
                            "(this is a bug — please report)")

    def generate(self, prompt_ids, max_new_tokens, temperature=None,
                 eos_id=None, deadline_s=None, timeout=None):
        """Blocking single-request generation. Returns the FULL sequence
        (prompt + generated tokens) as a 1D int32 numpy array — the
        per-request view of what `GPTDecoder.generate` returns for a
        batch."""
        req = self.submit(prompt_ids, max_new_tokens,
                          temperature=temperature, eos_id=eos_id,
                          deadline_s=deadline_s)
        self._drive_until([req], timeout=timeout)
        toks = req.result()               # raises on failure
        return onp.concatenate([onp.asarray(req.prompt, onp.int32),
                                onp.asarray(toks, onp.int32)])

    def generate_many(self, prompts, max_new_tokens, temperature=None,
                      eos_id=None, deadline_s=None, timeout=None):
        """Batch convenience: submit every prompt, drive to completion,
        return the list of full sequences (prompt order preserved even
        when completion is out of order)."""
        reqs = [self.submit(p, max_new_tokens, temperature=temperature,
                            eos_id=eos_id, deadline_s=deadline_s)
                for p in prompts]
        self._drive_until(reqs, timeout=timeout)
        outs = []
        for req in reqs:
            toks = req.result()
            outs.append(onp.concatenate([onp.asarray(req.prompt, onp.int32),
                                         onp.asarray(toks, onp.int32)]))
        return outs

    def iter_tokens(self, handle: Request, timeout=30.0):
        """Stream `handle`'s tokens as the engine produces them.

        With a background driver running, this just blocks on the
        stream; without one, the consuming thread steps the engine
        itself. Raises the request's error (deadline, shutdown) at the
        point of failure; `timeout` bounds the wait for any single
        token."""
        while True:
            try:
                item = handle._stream.get_nowait()
            except _queue.Empty:
                if self._driver_running() or handle.done:
                    try:
                        item = handle._stream.get(timeout=timeout)
                    except _queue.Empty:
                        raise TimeoutError(
                            f"no token from request {handle.id} in "
                            f"{timeout}s (state={handle.state})") from None
                else:
                    self.step()
                    continue
            if item is _DONE:
                if handle.error is not None:
                    raise handle.error
                return
            yield item

    # -- driver thread ------------------------------------------------------

    def start(self):
        """Start the background driver thread: it owns the step loop so
        client threads only submit/stream. Idempotent."""
        if self._driver_running():
            return self
        self._stop.clear()

        def _idle():
            # nothing queued, nothing running (or a failed step): back off
            with tracing.phase("mx.serve.idle", timed=True) as idle:
                time.sleep(_IDLE_SLEEP_S)
            STEP_SECONDS["idle"].inc(idle.seconds)

        def _loop():
            import logging

            log = logging.getLogger("incubator_mxnet_tpu.serve")
            failures = 0
            while not self._stop.is_set():
                try:
                    progressed = self.step()
                    failures = 0
                except Exception as e:
                    # step() already flight-dumped; a transient fault
                    # (chaos seam, retryable fabric error) must not
                    # silently kill the driver thread — but a
                    # deterministic bug must not spin it forever either
                    failures += 1
                    log.error(
                        "serve driver: step failed (%d consecutive): "
                        "%s: %s", failures, type(e).__name__, e)
                    if failures >= _DRIVER_MAX_CONSECUTIVE_FAILURES:
                        log.error(
                            "serve driver: stopping after %d consecutive "
                            "step failures — in-flight requests need a "
                            "manual step()/start() after the cause is "
                            "fixed", failures)
                        break
                    _idle()
                    continue
                if not progressed:
                    _idle()

        self._driver = threading.Thread(target=_loop, name="mx-serve-driver",
                                        daemon=True)
        self._driver.start()
        return self

    def stop(self):
        """Stop the driver thread (requests stay queued/running; call
        `step()` manually or `start()` again to resume)."""
        self._stop.set()
        d = self._driver
        if d is not None:
            d.join(timeout=5.0)
        self._driver = None

    # -- lifecycle ----------------------------------------------------------

    def shutdown(self, drain=True, timeout=None):
        """Stop the engine. ``drain=True`` finishes the requests already
        occupying slots (new work and the never-admitted queue are
        rejected with `EngineClosed`); ``drain=False`` fails everything
        immediately. Releases the device KV cache."""
        with self._lock:
            self._sched.close(drain=drain)
            running = [r for r in self._sched._in_slot if r is not None]
        if drain and running:
            t_end = None if timeout is None else time.monotonic() + timeout
            while True:
                with self._lock:
                    if self._sched.n_active == 0:
                        break
                if t_end is not None and time.monotonic() > t_end:
                    raise TimeoutError(
                        f"drain did not finish in {timeout}s "
                        f"({self._sched.n_active} slots still busy)")
                if not self._driver_running():
                    self.step()
                else:
                    time.sleep(0.01)
        self.stop()
        with self._lock:
            # drop the prefix cache's page references before the pool
            # itself: a clean shutdown leaves the allocator empty
            self._sched.slots.prefix_cache.clear()
            self._sched.slots.release()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.shutdown(drain=exc_type is None)
