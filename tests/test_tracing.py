"""Span tracing + flight recorder + SLO tracker (ISSUE 5).

Layers, cheapest first:

- tracer mechanics: nesting/IDs (incl. across threads), explicit
  open_span lifecycle, ring bounds, chrome export clock base — all pure
  host, `quick`-marked;
- off-path contract: MXNET_TELEMETRY unset ⇒ every probe is one enabled
  check: the shared null span, no clock read, zero spans recorded;
- serve request traces against the stub scheduler (quick) AND the real
  compiled engine, where the zero-steady-state-recompile gate
  (`xla_program_count`) must hold WITH tracing enabled;
- flight recorder: an injected `serve_step` fault leaves a dump holding
  the active request's spans; `estimator_step` crash-resume dumps too;
- SLO burn math + the loud health-monitor hook;
- training lifecycle spans: estimator epoch/step, dataloader batch,
  kvstore push/pull/barrier, checkpoint write/resume.
"""
import json
import os
import threading
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import np
from incubator_mxnet_tpu.telemetry import monitor, registry, slo, tracing

VOCAB = 97


@pytest.fixture(autouse=True)
def _clean_tracing():
    yield
    tracing.disable()
    tracing.reset()
    slo.tracker().clear()
    monitor.remove_health_check("slo")


def _span_names(trace_id=None):
    return [s.name for s in tracing.finished_spans(trace_id)]


# ---------------------------------------------------------------------------
# tracer mechanics
# ---------------------------------------------------------------------------

def test_span_nesting_and_ids():
    tracing.enable()
    with tracing.span("outer", kind="t") as outer:
        assert tracing.current_span() is outer
        assert tracing.current_trace_id() == outer.trace_id
        with tracing.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            assert inner.span_id != outer.span_id
            inner.event("mark", n=1)
    assert tracing.current_span() is None
    spans = tracing.finished_spans(outer.trace_id)
    assert [s.name for s in spans] == ["outer", "inner"]  # start-ordered
    assert all(s.dur_ns is not None and s.dur_ns >= 0 for s in spans)
    assert spans[1].events and spans[1].events[0][0] == "mark"
    # sibling traces do not share ids
    with tracing.span("other") as other:
        pass
    assert other.trace_id != outer.trace_id


def test_spans_across_threads_join_one_trace():
    """The serve pattern: a root opened on one thread, children created
    on another via explicit parent= — one trace, distinct span ids."""
    tracing.enable()
    root = tracing.open_span("request", lane="req 0")

    def worker():
        with tracing.span("work", parent=root):
            pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    root.close()
    spans = tracing.finished_spans(root.trace_id)
    assert len(spans) == 5                       # root + 4 workers
    kids = [s for s in spans if s.name == "work"]
    assert all(s.parent_id == root.span_id for s in kids)
    assert len({s.span_id for s in spans}) == 5  # ids unique
    assert all(s.lane == "req 0" for s in kids)  # lane inherits


def test_open_span_explicit_lifecycle_and_ring_bound():
    tracing.enable()
    s = tracing.open_span("explicit")
    assert s in tracing.open_spans()
    assert tracing.current_span() is None        # never ambient
    s.close()
    s.close()                                    # idempotent
    assert s not in tracing.open_spans()
    # ring stays bounded
    for i in range(tracing.RING_CAPACITY + 50):
        with tracing.span("burst"):
            pass
    mine = [x for x in tracing.finished_spans() if x.name == "burst"]
    assert len(mine) <= tracing.RING_CAPACITY


def test_error_annotation_on_exception():
    tracing.enable()
    with pytest.raises(ValueError):
        with tracing.span("boom") as s:
            raise ValueError("kaput")
    assert s.attrs["error"] == "ValueError"
    assert "kaput" in s.attrs["error_msg"]


def test_chrome_export_lanes_and_clock_base():
    tracing.enable()
    t_before = time.time() * 1e6
    with tracing.span("laned", lane="req 7", foo="bar"):
        tracing.event("tick")
    ev = tracing.chrome_events()
    xs = [e for e in ev if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["name"] == "laned"
    assert xs[0]["args"]["foo"] == "bar"
    # epoch µs, derived from the span's perf_counter reading — the epoch
    # the profiler rebases its device events onto
    assert t_before <= xs[0]["ts"] <= time.time() * 1e6
    names = [e for e in ev if e["ph"] == "M" and e["name"] == "thread_name"]
    assert any(m["args"]["name"] == "req 7" for m in names)
    assert any(e["ph"] == "i" and e["name"] == "tick" for e in ev)
    payload = tracing.chrome_trace(include_device=True)
    assert {e["name"] for e in payload["traceEvents"]} >= {"laned", "tick"}


def test_committed_timeline_example_loads_and_shares_clock():
    """The acceptance artifact: benchmark/trace_timeline_example.json
    holds host request spans AND XLA device slices on one clock base."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark",
        "trace_timeline_example.json")
    with open(path) as f:
        payload = json.load(f)
    ev = payload["traceEvents"]
    spans = [e for e in ev if e.get("pid") == 2 and e.get("ph") == "X"]
    device = [e for e in ev if e.get("pid", 0) >= 1000
              and e.get("ph") == "X"]
    assert any(e["name"] == "serve.request" for e in spans)
    assert any(e["name"] == "serve.prefill" for e in spans)
    assert device, "no device slices in the committed example"
    lo = min(e["ts"] for e in spans)
    hi = max(e["ts"] + e.get("dur", 0) for e in spans)
    overlapping = [e for e in device if lo <= e["ts"] <= hi]
    # shared clock base: the device slices sit under the request spans
    assert len(overlapping) > 100, (len(overlapping), len(device))


# ---------------------------------------------------------------------------
# off-path contract: with MXNET_TELEMETRY unset a probe is one flag check
# (structural: a CPU run gives counts, not speeds — PERF.md §1)
# ---------------------------------------------------------------------------

def test_off_path_records_nothing_and_reads_no_clock(monkeypatch,
                                                     count_clock_reads):
    assert not tracing.is_enabled()
    clock = count_clock_reads(tracing)
    notes = []
    monkeypatch.setattr(tracing, "_note", lambda name: notes.append(name))
    for i in range(300):
        # the literal instrumented-site patterns, disabled
        with tracing.span("estimator.step", batch=i) as s:
            tracing.event("ghost-event")
            tracing.annotate(x=2)
        o = tracing.open_span("serve.request", request=i)
        r = tracing.record_span("serve.decode_step", 1.0, 2.0, slots=i)
        # every probe hands back THE shared null span: no allocation
        assert s is o is r is tracing._NULL_SPAN and not s
        assert o.annotate(x=1).close() is o
    assert clock.reads == 0 and notes == []
    assert tracing.finished_spans() == [] and tracing.open_spans() == []
    assert tracing.maybe_flight_dump("nope") is None


def test_armed_span_reads_the_clock_once_at_each_end(count_clock_reads):
    tracing.enable()
    clock = count_clock_reads(tracing)
    with tracing.span("outer"):
        pass
    assert clock.reads == 2                     # start and end
    clock.reads = 0
    tracing.record_span("stamped", 1.0, 1.5)    # both stamps handed in
    with tracing.span("half", t0=time.perf_counter()):
        pass
    assert clock.reads == 1                     # only the end of `half`
    stamped = [s for s in tracing.finished_spans() if s.name == "stamped"][0]
    assert stamped.t0_ns == int(1e9) and stamped.dur_ns == int(0.5e9)
    assert stamped.t0_us == pytest.approx(1e6 + tracing._EPOCH_US)


# ---------------------------------------------------------------------------
# serve request traces — stub scheduler (quick) + real compiled engine
# ---------------------------------------------------------------------------

class _StubSlots:
    """Paged-interface stub: pure host arithmetic over a REAL page
    allocator + prefix cache (host-only classes); the final prefill
    chunk emits the prompt's length as the first token, decode
    increments."""

    max_slots, max_len = 2, 64
    page_tokens, prefill_chunk = 16, 64

    def __init__(self):
        from incubator_mxnet_tpu import serve

        pages_per_slot = -(-self.max_len // self.page_tokens)
        self.allocator = serve.PageAllocator(
            self.max_slots * pages_per_slot + 1, self.page_tokens)
        self.prefix_cache = serve.PrefixCache(self.allocator)

    def set_slot_pages(self, slot, pages):
        pass

    def clear_slot(self, slot):
        pass

    def prefill_chunk_step(self, slot, chunk_tokens, t_start, key,
                           temperature=1.0):
        n = len(chunk_tokens)
        return int(t_start) + n, n, 0

    def fetch_tokens(self, out):
        return onp.asarray(out)

    def fetch_first(self, out):
        return int(out)

    def decode_step(self, last, pos, active, key, temps):
        return onp.where(active, last + 1, last).astype(onp.int32)

    def xla_program_count(self):
        return 0

    def release(self):
        pass


def _prompt(n, seed=0):
    return onp.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(onp.int32)


def test_serve_request_trace_stub():
    """One trace per request with the full lifecycle — no XLA, quick."""
    from incubator_mxnet_tpu.serve.scheduler import Scheduler

    tracing.enable()
    sched = Scheduler(_StubSlots(), max_queue=16)
    reqs = [sched.submit(_prompt(4 + i, seed=i), 3) for i in range(5)]
    while not all(r.done for r in reqs):
        sched.step()
    for r in reqs:
        assert r.trace_id is not None
        names = sorted(_span_names(r.trace_id))
        assert names == ["serve.decode", "serve.prefill", "serve.queue",
                         "serve.request"], names
        root = [s for s in tracing.finished_spans(r.trace_id)
                if s.name == "serve.request"][0]
        assert root.attrs["tokens"] == 3
        assert root.attrs["reason"] == "length"
        assert root.lane == f"req {r.id}"
    # traces are distinct per request
    assert len({r.trace_id for r in reqs}) == len(reqs)
    # engine-level spans exist alongside
    assert "serve.step" in _span_names()
    assert "serve.decode_step" in _span_names()


def test_serve_trace_deadline_failure_annotated():
    from incubator_mxnet_tpu.serve.scheduler import (DeadlineExceeded,
                                                     Scheduler)

    tracing.enable()
    sched = Scheduler(_StubSlots(), max_queue=8)
    req = sched.submit(_prompt(4), 4, deadline_s=0.0)
    time.sleep(0.005)
    sched.step()
    assert req.state == "failed"
    root = [s for s in tracing.finished_spans(req.trace_id)
            if s.name == "serve.request"][0]
    assert root.attrs["error"] == DeadlineExceeded.__name__
    # never admitted: queue span closed, no prefill/decode segments
    names = _span_names(req.trace_id)
    assert "serve.queue" in names and "serve.prefill" not in names


@pytest.fixture(scope="module")
def net():
    """Same spicy-weights recipe as test_serve.py (non-degenerate greedy
    paths through the real compiled slot programs)."""
    from incubator_mxnet_tpu.models.gpt import gpt_tiny

    mx.random.seed(11)
    m = gpt_tiny(vocab_size=VOCAB, max_length=64, dropout=0.0)
    m.initialize()
    r = onp.random.RandomState(42)
    for _name, p in m.collect_params().items():
        if p.shape and len(p.shape) >= 2:
            p.set_data(np.array(
                r.normal(0, 0.35, p.shape).astype("float32")))
    return m


def test_real_engine_traced_requests_and_recompile_gate(net):
    """The acceptance gate: tracing ON, every request gets a complete
    trace, and the engine's compiled-program count is IDENTICAL to the
    untraced steady state (host-side spans only — nothing enters jit)."""
    from incubator_mxnet_tpu import serve

    eng = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32)
    try:
        # warm both prefill buckets + decode UNTRACED
        eng.generate(_prompt(5, seed=9), 3)
        eng.generate(onp.resize(_prompt(5, seed=9), 40), 3)
        warm_count = eng.xla_program_count()
        assert warm_count >= 2

        tracing.enable()
        prompts = [_prompt(int(onp.random.RandomState(i).randint(3, 18)),
                           seed=i) for i in range(6)]
        handles = [eng.submit(p, 4) for p in prompts]
        eng._drive_until(handles)
        for h in handles:
            assert h.error is None
            names = sorted(_span_names(h.trace_id))
            assert names == ["serve.decode", "serve.prefill",
                             "serve.queue", "serve.request"], names
            prefill = [s for s in tracing.finished_spans(h.trace_id)
                       if s.name == "serve.prefill"][0]
            # the chunk-bucket program that served the prompt's last
            # chunk, annotated by the scheduler
            assert prefill.attrs["bucket"] in (16, 32, 64)
            assert prefill.attrs["chunks"] >= 1
        # zero steady-state recompiles WITH tracing enabled
        assert eng.xla_program_count() == warm_count
    finally:
        eng.shutdown(drain=False)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_dump_noop_while_disabled(tmp_path):
    assert not tracing.is_enabled()
    assert tracing.maybe_flight_dump("nope") is None


def test_flight_recorder_on_injected_serve_fault(net, tmp_path):
    """An injected serve_step fault leaves flightrec_*.json holding the
    active (still-open) request trace — the postmortem the ISSUE asks
    for."""
    from incubator_mxnet_tpu import fault, serve
    from incubator_mxnet_tpu.test_utils import environment

    tracing.enable()
    with environment("MXNET_FLIGHTREC_DIR", str(tmp_path)):
        eng = serve.ServeEngine(net, max_slots=2, max_len=64, max_queue=8)
        try:
            req = eng.submit(_prompt(6, seed=3), 4)   # queued, not stepped
            fault.configure_injection("serve_step:1.0:0:1")
            try:
                with pytest.raises(fault.FaultInjected):
                    eng.step()
            finally:
                fault.clear_injection()
            dumps = list(tmp_path.glob("flightrec_serve_step_*.json"))
            assert len(dumps) == 1
            with open(dumps[0]) as f:
                payload = json.load(f)
            assert payload["error"]["type"] == "FaultInjected"
            # the armed chaos schedule rides along in the dump
            assert payload["fault_schedule"]["serve_step"]["fired"] == 1
            open_names = {s["name"] for s in payload["open_spans"]}
            # the queued request's trace is the in-flight context
            assert {"serve.request", "serve.queue"} <= open_names
            assert any(s.get("attrs", {}).get("request") == req.id
                       for s in payload["open_spans"]
                       if s["name"] == "serve.request")
            # the fault event itself is in the dump (on the serve.step
            # span that crashed)
            all_events = [ev for s in payload["spans"]
                          for ev in s.get("events", [])]
            assert any(ev["name"] == "fault.injected"
                       and ev["attrs"].get("seam") == "serve_step"
                       for ev in all_events)
            # the engine recovers on the next clean step
            eng._drive_until([req])
            assert req.error is None
        finally:
            eng.shutdown(drain=False)


def test_flight_recorder_on_estimator_crash_resume(tmp_path):
    """ResilienceHandler's crash-resume drops a flight dump BEFORE
    rewinding to the checkpoint (estimator_step seam)."""
    from incubator_mxnet_tpu import fault, gluon, preemption
    from incubator_mxnet_tpu.fault.resilience import ResilienceHandler
    from incubator_mxnet_tpu.gluon.contrib.estimator import Estimator
    from incubator_mxnet_tpu.test_utils import environment

    tracing.enable()
    net = gluon.nn.Dense(1, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    est = Estimator(net, loss=gluon.loss.L2Loss(), trainer=trainer)
    import logging

    est.logger.setLevel(logging.CRITICAL)
    ckpt = preemption.TrainingCheckpointer(
        str(tmp_path / "ck"), net, trainer, every_n=1,
        register_signal=False)
    X = np.array(onp.random.RandomState(0)
                 .uniform(-1, 1, (32, 4)).astype("float32"))
    Y = np.array(onp.zeros((32, 1), "float32"))
    loader = gluon.data.DataLoader(
        gluon.data.ArrayDataset(X, Y), batch_size=8)
    with environment({"MXNET_FLIGHTREC_DIR": str(tmp_path),
                      "MXNET_RETRY_BASE_DELAY_MS": "1"}):
        fault.configure_injection("estimator_step:1.0:0:1")
        try:
            est.fit(loader, epochs=1, event_handlers=[
                ResilienceHandler(checkpointer=ckpt, max_resumes=2)])
        finally:
            fault.clear_injection()
    dumps = list(tmp_path.glob("flightrec_estimator_crash_*.json"))
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        payload = json.load(f)
    assert payload["error"]["type"] == "FaultInjected"
    crashed = [s for s in payload["spans"]
               if s["name"] == "estimator.step"
               and s.get("attrs", {}).get("error") == "FaultInjected"]
    assert crashed, [s["name"] for s in payload["spans"]]
    assert any(ev["name"] == "fault.injected"
               for ev in crashed[0]["events"])


# ---------------------------------------------------------------------------
# SLO tracker
# ---------------------------------------------------------------------------

def test_slo_latency_burn_math():
    h = registry.histogram("t_slo_ttft_seconds", buckets=(0.1, 0.5, 1.0))
    for _ in range(96):
        h.observe(0.05)
    for _ in range(4):
        h.observe(0.7)                 # 4% bad against a 0.1s threshold
    # target 0.90: budget 10%, bad 4% -> burn 0.4, holds
    r = slo.tracker().latency("lat90", "t_slo_ttft_seconds", 0.1,
                              target=0.90).evaluate()
    assert r["compliance"] == pytest.approx(0.96)
    assert r["burn"] == pytest.approx(0.4)
    assert r["ok"]
    # target 0.99: budget 1%, bad 4% -> burn 4.0, violated
    r2 = slo.tracker().latency("lat99", "t_slo_ttft_seconds", 0.1,
                               target=0.99).evaluate()
    assert r2["burn"] == pytest.approx(4.0)
    assert not r2["ok"]
    # gauges surfaced in the registry
    rep = registry.report()
    assert rep['mx_slo_error_budget_burn{slo="lat99"}']["value"] \
        == pytest.approx(4.0)
    assert rep['mx_slo_ok{slo="lat99"}']["value"] == 0
    assert rep['mx_slo_ok{slo="lat90"}']["value"] == 1
    # no data yet -> no violation, compliance None
    r3 = slo.tracker().latency("lat_empty", "t_slo_never_seen",
                               0.1).evaluate()
    assert r3["compliance"] is None and r3["ok"]


def test_slo_throughput_windows(monkeypatch):
    c = registry.counter("t_slo_tokens_total")
    s = slo.tracker().throughput("tput", "t_slo_tokens_total",
                                 min_rate=100.0, target=0.5)
    now = [1000.0]
    # `_measure` reads the clock itself: the test's, not a host's whose
    # monotonic clock (its uptime) may still stand under 1000 s
    monkeypatch.setattr(slo.time, "monotonic", lambda: now[0])
    s.observe_window(now[0])           # prime
    c.inc(500)
    now[0] += 1.0
    rate = s.observe_window(now[0])    # 500/s: good window
    assert rate == pytest.approx(500.0)
    c.inc(10)
    now[0] += 1.0
    s.observe_window(now[0])           # 10/s: bad window
    now[0] += 1.0
    comp, detail = s._measure()        # adds one more (bad) window
    assert detail["windows"] == 3 and detail["good"] == 1
    assert comp == pytest.approx(1 / 3)


def test_slo_health_hook_raises_loudly():
    h = registry.histogram("t_slo_bad_seconds", buckets=(0.1, 1.0))
    for _ in range(10):
        h.observe(0.9)                 # 100% bad
    slo.tracker().latency("all_bad", "t_slo_bad_seconds", 0.1,
                          target=0.99)
    slo.install_health_check()
    with pytest.raises(mx.MXNetError, match="all_bad"):
        monitor.check()
    # uninstalling restores a clean check()
    monitor.remove_health_check("slo")
    monitor.check()
    assert slo.violations()            # the tracker itself still reports


def test_slo_presets_register():
    a = slo.serve_ttft(threshold_s=0.25)
    b = slo.step_time(threshold_s=1.0)
    assert a.series == "mx_serve_ttft_seconds"
    assert b.series == "mx_step_time_seconds"
    names = {s.name for s in slo.tracker().slos()}
    assert {"serve_ttft", "step_time"} <= names
    with pytest.raises(ValueError):
        slo.serve_ttft()               # duplicate name is loud


# ---------------------------------------------------------------------------
# training lifecycle spans
# ---------------------------------------------------------------------------

def test_estimator_and_dataloader_spans():
    import logging

    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon.contrib.estimator import Estimator

    tracing.enable()
    net = gluon.nn.Dense(1, in_units=4)
    net.initialize()
    est = Estimator(net, loss=gluon.loss.L2Loss(),
                    trainer=gluon.Trainer(net.collect_params(), "sgd",
                                          {"learning_rate": 0.01}))
    est.logger.setLevel(logging.CRITICAL)
    X = np.array(onp.random.RandomState(0)
                 .uniform(-1, 1, (64, 4)).astype("float32"))
    Y = np.array(onp.zeros((64, 1), "float32"))
    loader = gluon.data.DataLoader(
        gluon.data.ArrayDataset(X, Y), batch_size=16)
    est.fit(loader, epochs=2)
    names = _span_names()
    assert names.count("estimator.epoch") == 2
    steps = [s for s in tracing.finished_spans()
             if s.name == "estimator.step"]
    assert len(steps) == 8                     # 4 batches x 2 epochs
    epochs = [s for s in tracing.finished_spans()
              if s.name == "estimator.epoch"]
    # steps nest under their epoch
    assert all(any(st.parent_id == ep.span_id for ep in epochs)
               for st in steps)
    assert "dataloader.batch" in names


def test_kvstore_and_checkpoint_spans(tmp_path):
    from incubator_mxnet_tpu import kv, preemption

    tracing.enable()
    store = kv.create("local")
    store.init("w", np.array([1.0, 2.0]))
    store.push("w", np.array([0.1, 0.2]))
    store.pull("w")
    store.barrier()
    preemption.atomic_save(
        str(tmp_path / "ck.bin"),
        lambda p: open(p, "wb").write(b"x" * 16))
    names = _span_names()
    for expected in ("kvstore.push", "kvstore.pull", "kvstore.barrier",
                     "checkpoint.write"):
        assert expected in names, (expected, names)


def test_retry_events_annotate_span(tmp_path):
    from incubator_mxnet_tpu.fault.retry import RetryPolicy

    tracing.enable()
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise ConnectionError("transient")
        return "ok"

    with tracing.span("op") as s:
        out = RetryPolicy(max_retries=3, base_delay=0.0, jitter=0.0,
                          name="test").call(flaky)
    assert out == "ok"
    retries = [e for e in s.events if e[0] == "retry"]
    assert len(retries) == 2
    assert retries[0][2]["policy"] == "test"


# ---------------------------------------------------------------------------
# env knobs
# ---------------------------------------------------------------------------

def test_telemetry_dump_knob_snapshots(tmp_path):
    path = str(tmp_path / "metrics.prom")
    registry.counter("t_dump_knob_total").inc(5)
    p, interval = registry.arm_textfile_dump(f"{path}:0.05")
    try:
        assert p == path and interval == pytest.approx(0.05)
        with open(path) as f:
            assert "t_dump_knob_total 5" in f.read()
        registry.counter("t_dump_knob_total").inc(2)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with open(path) as f:
                if "t_dump_knob_total 7" in f.read():
                    break
            time.sleep(0.02)
        else:
            pytest.fail("periodic dump never refreshed")
    finally:
        registry.stop_textfile_dump()
    # one-shot form (no interval)
    p2, i2 = registry.arm_textfile_dump(str(tmp_path / "once.prom"))
    assert i2 is None and os.path.exists(p2)
    registry.stop_textfile_dump()


def test_env_knobs_registered():
    from incubator_mxnet_tpu import util

    knobs = util.env_knobs()
    for k in ("MXNET_TELEMETRY_DUMP", "MXNET_FLIGHTREC_DIR"):
        assert k in knobs
        assert not knobs[k][0].startswith("(")   # honored


def test_mxnet_telemetry_env_arms_tracing():
    """MXNET_TELEMETRY=1 arms span tracing at import
    (util._apply_env_config) — same knob as stage tracing."""
    from incubator_mxnet_tpu import util
    from incubator_mxnet_tpu.telemetry import stages
    from incubator_mxnet_tpu.test_utils import environment

    assert not tracing.is_enabled()
    with environment("MXNET_TELEMETRY", "1"):
        util._apply_env_config()
    try:
        assert tracing.is_enabled()
        assert stages.is_enabled()
    finally:
        tracing.disable()
        stages.disable()


# ---------------------------------------------------------------------------
# ignored-arg loudness (satellite: VERDICT "dishonest surface")
# ---------------------------------------------------------------------------

def test_lazy_update_is_loud_once_and_counted():
    import warnings

    from incubator_mxnet_tpu.ndarray import optim_ops

    nd = mx.nd
    w = np.array(onp.ones((3,), "float32"))
    g = np.array(onp.ones((3,), "float32"))
    before = registry.counter("mx_ignored_arg_total",
                              labels={"arg": "lazy_update"}).value
    optim_ops._WARNED_IGNORED.discard("lazy_update")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        nd.sgd_update(w, g, lr=0.1, lazy_update=True)
        nd.sgd_update(w, g, lr=0.1, lazy_update=False)   # warn ONCE only
    loud = [x for x in rec if "lazy_update" in str(x.message)]
    assert len(loud) == 1
    assert "IGNORED" in str(loud[0].message)
    after = registry.counter("mx_ignored_arg_total",
                             labels={"arg": "lazy_update"}).value
    assert after - before == 2                 # every occurrence counted
    # not passing it stays silent and uncounted
    with warnings.catch_warnings(record=True) as rec2:
        warnings.simplefilter("always")
        nd.sgd_update(w, g, lr=0.1)
    assert not [x for x in rec2 if "lazy_update" in str(x.message)]
    assert registry.counter("mx_ignored_arg_total",
                            labels={"arg": "lazy_update"}).value == after
