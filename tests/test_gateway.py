"""serve.gateway — multi-tenant front door over co-resident engines
(ISSUE 9).

Three layers of coverage, all deterministic on CPU:

- host-only unit tests for the tenancy primitives (`parse_tiers`,
  `parse_quota`, `TokenBucket`, `WDRRQueue`): weighted deficit round
  robin converges to the weights, quotas defer (never drop), starved
  outsized heads make progress;
- gateway-logic tests against the stub slot decoder (pure host
  arithmetic, no XLA compile — the `quick`-marked ones): tier-ordered
  dispatch, preemption that keeps tokens and re-queues remaining work,
  the deadline-while-preempted classification (DeadlineExceeded,
  retryable — never an eviction error), per-tenant quota throttling,
  labeled queue-depth gauges, the `gateway_step` fault seam, gateway
  spans joining the per-request trace, and the flight-recorder context;
- the trace-replay ACCEPTANCE GATE on real compiled engines: two
  co-resident tiny GPTs, three tenants across three tiers on a recorded
  trace — every request completes or fails loudly, the high tier's TTFT
  p99 under contention stays within 1.5× its solo value, preempted
  low-priority requests all finish, the per-engine zero-steady-state-
  recompile gate holds, and the `slo.gateway_ttft` error budget is
  compliant for the high tier.
"""
import json
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import np, serve
from incubator_mxnet_tpu.models.gpt import gpt_tiny
from incubator_mxnet_tpu.serve import tenancy
from incubator_mxnet_tpu.serve.engine import (PageAllocator,
                                              PagePoolExhausted,
                                              PrefixCache)
from incubator_mxnet_tpu.serve.scheduler import (DeadlineExceeded,
                                                 EngineClosed, QueueFull)
from incubator_mxnet_tpu.telemetry import registry, slo, tracing

VOCAB = 97


# ---------------------------------------------------------------------------
# tenancy primitives — pure host (quick)
# ---------------------------------------------------------------------------

def test_parse_tiers_default_and_errors():
    assert tenancy.parse_tiers(None) == tenancy.DEFAULT_TIERS
    assert tenancy.parse_tiers("") == tenancy.DEFAULT_TIERS
    assert tenancy.parse_tiers("gold, silver ,bronze") == \
        ("gold", "silver", "bronze")
    with pytest.raises(ValueError):
        tenancy.parse_tiers("a,,b")
    with pytest.raises(ValueError):
        tenancy.parse_tiers("a,b,a")


def test_parse_quota():
    assert tenancy.parse_quota(None) == (None, None)
    assert tenancy.parse_quota("") == (None, None)
    assert tenancy.parse_quota("0") == (None, None)      # 0 = unmetered
    assert tenancy.parse_quota("100") == (100.0, 400.0)  # burst = 4×rate
    assert tenancy.parse_quota("100:50") == (100.0, 50.0)


def test_token_bucket_refill_debit_credit():
    b = tenancy.TokenBucket(10.0, 20.0)        # explicit virtual clock
    assert b.level(0.0) == 20.0                # starts full
    assert b.try_debit(15.0, 0.0)
    assert b.level(0.0) == 5.0
    assert not b.try_debit(10.0, 0.0)          # defer, level untouched
    assert b.level(0.0) == 5.0
    assert b.level(1.0) == 15.0                # +10 tokens/s refill
    b.credit(10.0)                             # refund caps at burst
    assert b.level(1.0) == 20.0
    # unmetered: no level, every debit succeeds
    free = tenancy.TokenBucket(None)
    assert free.level(0.0) is None
    assert free.try_debit(10**9, 0.0)
    with pytest.raises(ValueError):
        tenancy.TokenBucket(-1.0)
    with pytest.raises(ValueError):
        tenancy.Tenant("t", weight=0.0)


def test_wdrr_weighted_share():
    """Costs above the quantum make the weights visible: tenant a at
    weight 2 accumulates deficit twice as fast, so the pop sequence
    converges to a 2:1 token share."""
    q = tenancy.WDRRQueue(quantum=10)
    for i in range(6):
        q.push("a", ("a", i))
    for i in range(3):
        q.push("b", ("b", i))
    assert len(q) == 9
    w = {"a": 2.0, "b": 1.0}
    order = [q.pop_next(w, lambda r: 40.0, lambda r: True)[0]
             for _ in range(9)]
    assert order[:6] == ["a", "a", "b", "a", "a", "b"]
    assert order.count("a") == 6 and order.count("b") == 3
    assert len(q) == 0 and q.pop_next(w, lambda r: 1.0,
                                      lambda r: True) is None


def test_wdrr_starvation_fallback():
    """A lone head whose cost dwarfs the quantum still pops (its tenant
    pays by going deeply negative) — bounded unfairness over starvation."""
    q = tenancy.WDRRQueue(quantum=10)
    q.push("big", "x")
    assert q.pop_next({}, lambda r: 1000.0, lambda r: True) == "x"
    assert len(q) == 0


def test_wdrr_defers_without_burning_deficit():
    q = tenancy.WDRRQueue(quantum=10)
    q.push("a", "a0")
    q.push("b", "b0")
    # a's head is not dispatchable (quota/backlog): b pops, a's deficit
    # is NOT granted-and-lost — it simply waits
    got = q.pop_next({}, lambda r: 1.0, lambda r: r != "a0")
    assert got == "b0"
    assert q._deficit["a"] == 0.0
    assert q.pop_next({}, lambda r: 1.0, lambda r: False) is None
    assert q.items() == ["a0"]
    assert q.remove("a0") and not q.remove("a0")
    assert len(q) == 0


# ---------------------------------------------------------------------------
# gateway logic against a stub decoder (no XLA, quick)
# ---------------------------------------------------------------------------

class _StubSlots:
    """Paged-interface stand-in (same recipe as test_serve.py): pure
    host arithmetic over a REAL allocator/prefix cache. The final
    prefill chunk emits the prompt's length as the first token, decode
    increments — so a request preempted mid-decode and resumed from
    ``prompt + tokens`` must continue the same arithmetic run."""

    def __init__(self, max_slots=2, max_len=64, page_tokens=16,
                 prefill_chunk=64):
        self.max_slots = max_slots
        self.max_len = max_len
        self.page_tokens = page_tokens
        self.prefill_chunk = prefill_chunk
        pages_per_slot = -(-max_len // page_tokens)
        self.allocator = PageAllocator(max_slots * pages_per_slot + 1,
                                       page_tokens)
        self.prefix_cache = PrefixCache(self.allocator)

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

    def decode_step(self, last_tok, pos, active, key, temperature):
        return onp.where(active, last_tok + 1, last_tok).astype(onp.int32)

    def xla_program_count(self):
        return 0

    def release(self):
        pass


def _prompt(n, seed=0):
    return onp.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(onp.int32)


def _stub_gateway(max_slots=2, **gw_kwargs):
    reg = serve.ModelRegistry()
    reg.add("m", _StubSlots(max_slots=max_slots))
    return serve.Gateway(reg, **gw_kwargs)


def test_gateway_constructor_validation():
    with pytest.raises(TypeError):
        serve.Gateway(object())
    with pytest.raises(ValueError):
        serve.Gateway(serve.ModelRegistry())          # empty registry
    reg = serve.ModelRegistry()
    reg.add("m", _StubSlots())
    with pytest.raises(ValueError):
        reg.add("m", _StubSlots())                    # duplicate name
    with pytest.raises(ValueError):
        reg.add("m2", _StubSlots(), share=0.0)
    # engine kwargs cannot retarget a pre-built decoder
    reg2 = serve.ModelRegistry()
    reg2.add("m", _StubSlots(), max_slots=4)
    with pytest.raises(ValueError) as ei:
        serve.Gateway(reg2)
    assert "pre-built" in str(ei.value)


def test_gateway_custom_tiers_and_env_knobs():
    from incubator_mxnet_tpu.test_utils import environment

    gw = _stub_gateway(tiers="gold,bronze")
    assert gw.tiers == ("gold", "bronze")
    h = gw.submit("m", _prompt(4), 1)           # default = middle tier
    assert h.priority == "bronze"
    gw._drive_until([h], timeout=10)
    with environment({"MXNET_SERVE_PRIORITY_TIERS": "x,y,z",
                      "MXNET_GATEWAY_PREEMPT": "0"}):
        gw2 = _stub_gateway()
        assert gw2.tiers == ("x", "y", "z")
        assert not gw2.preempt_enabled


def test_gateway_submit_validation():
    gw = _stub_gateway()
    with pytest.raises(ValueError):
        gw.submit("nope", _prompt(4), 2)              # unknown model
    with pytest.raises(ValueError):
        gw.submit("m", _prompt(4), 2, priority="vip")  # unknown tier
    with pytest.raises(ValueError):
        gw.submit("m", onp.zeros((0,), onp.int32), 2)
    with pytest.raises(ValueError):
        gw.submit("m", _prompt(4), 0)
    with pytest.raises(ValueError):
        gw.submit("m", _prompt(60), 10)               # 70 > max_len 64
    # a request that could NEVER fit the model's page pool is rejected
    # at submit with the loud PagePoolExhausted, not deferred forever
    stub = _StubSlots(max_slots=1)
    stub.allocator = PageAllocator(3, 16)             # 2 usable pages
    stub.prefix_cache = PrefixCache(stub.allocator)
    reg = serve.ModelRegistry()
    reg.add("tiny", stub)
    gw2 = serve.Gateway(reg)
    with pytest.raises(PagePoolExhausted):
        gw2.submit("tiny", _prompt(30), 10)


def test_gateway_queue_backpressure_raises():
    from incubator_mxnet_tpu.fault.retry import classify_exception

    gw = _stub_gateway(max_queue=2)
    gw.submit("m", _prompt(4), 2)
    gw.submit("m", _prompt(5), 2)
    with pytest.raises(QueueFull) as ei:
        gw.submit("m", _prompt(6), 2)
    assert "capacity" in str(ei.value)
    assert classify_exception(ei.value) == "retryable"


def test_gateway_roundtrip_stub():
    gw = _stub_gateway()
    d0 = registry.counter("mx_gateway_dispatch_total",
                          labels={"model": "m",
                                  "priority": "normal"}).value
    out = gw.generate("m", _prompt(4), 3, tenant="acme")
    # stub arithmetic: first token = prompt len, then +1 per decode
    assert list(out[-3:]) == [4, 5, 6]
    assert out.dtype == onp.int32 and out.shape == (7,)
    t = gw.tenant("acme")
    assert t.dispatched == 1 and t.tokens_out == 3
    d1 = registry.counter("mx_gateway_dispatch_total",
                          labels={"model": "m",
                                  "priority": "normal"}).value
    assert d1 == d0 + 1


def test_priority_dispatch_order():
    """With preemption off, tier order still rules dispatch: when the
    single slot frees, the queued high request beats the earlier-queued
    low one."""
    gw = _stub_gateway(max_slots=1, preempt=False)
    a = gw.submit("m", _prompt(4), 4, priority="normal")
    gw.step()
    assert a.state == "dispatched"
    b = gw.submit("m", _prompt(5), 2, priority="low")
    c = gw.submit("m", _prompt(6), 2, priority="high")
    while not a.done:
        gw.step()
    gw.step()
    # the high request took the freed slot (a short one may even finish
    # within the step); the earlier-queued low one is still waiting
    assert c.state in ("dispatched", "done") and b.state == "queued"
    gw._drive_until([b, c], timeout=10)
    assert b.result() == [5, 6] and c.result() == [6, 7]


def test_preemption_resumes_with_tokens_intact():
    """The tentpole semantics: a high-tier arrival preempts the running
    low-tier slot; the victim keeps its tokens, re-enters the queue as
    remaining-chunk work, and its final stream is CONTINUOUS — exactly
    what an uninterrupted run would have produced."""
    gw = _stub_gateway(max_slots=1)
    low = gw.submit("m", _prompt(4), 8, tenant="crawl", priority="low")
    gw.step()
    # one step = prefill + one decode in the stub: two tokens in flight
    assert low.state == "dispatched" and low.tokens == [4, 5]
    ev0 = registry.counter("mx_serve_evictions_total",
                           labels={"reason": "preempted"}).value
    high = gw.submit("m", _prompt(6, seed=1), 3, tenant="acme",
                     priority="high")
    gw.step()
    # the victim is back in the queue with its progress intact ...
    assert low.state == "queued" and low.preemptions == 1
    assert low.tokens == [4, 5]
    assert high.state == "dispatched"
    # ... accounted everywhere the operator looks
    assert gw.preemptions_total == 1
    assert gw.tenant("crawl").preempted == 1
    ev1 = registry.counter("mx_serve_evictions_total",
                           labels={"reason": "preempted"}).value
    assert ev1 == ev0 + 1
    gw._drive_until([low, high], timeout=10)
    assert high.result() == [6, 7, 8]
    # continuity across the preemption: resume prefilled prompt+tokens,
    # so the stream is the same run an undisturbed request produces
    assert low.result() == list(range(4, 12))
    assert low.state == "done" and len(low.tokens) == low.max_new


def test_preempted_deadline_expiry_classifies_retryable():
    """A preempted request whose deadline expires while RE-QUEUED fails
    as DeadlineExceeded (retryable) — never an eviction/shutdown error:
    the preemption was the gateway's choice, not the client's fault."""
    gw = _stub_gateway(max_slots=1)
    low = gw.submit("m", _prompt(4), 8, tenant="crawl", priority="low",
                    deadline_s=0.3)
    gw.step()
    high = gw.submit("m", _prompt(6, seed=1), 30, tenant="acme",
                     priority="high")
    gw.step()
    assert low.state == "queued" and low.preemptions == 1
    time.sleep(0.35)
    gw.step()                                   # expiry sweep
    assert low.state == "failed"
    assert isinstance(low.error, DeadlineExceeded)
    assert not isinstance(low.error, EngineClosed)
    assert low.error_class == "retryable"
    assert "preemption" in str(low.error)
    with pytest.raises(DeadlineExceeded):
        low.result()
    gw._drive_until([high], timeout=10)
    assert len(high.tokens) == 30


def test_tenant_quota_defers_never_drops():
    """An over-quota tenant's request WAITS for the bucket to refill —
    it is never dropped — while unmetered tenants flow past it."""
    gw = _stub_gateway(tenants={"q": {"rate": 40.0, "burst": 8.0}})
    r1 = gw.submit("m", _prompt(4), 4, tenant="q")     # est cost 8
    r2 = gw.submit("m", _prompt(4), 4, tenant="q")     # bucket empty
    free = gw.submit("m", _prompt(5), 2, tenant="free")
    gw.step()
    assert r1.state == "dispatched"
    assert free.state != "queued"              # unmetered: not throttled
    assert r2.state == "queued"                # deferred, not dropped
    while not r1.done:
        gw.step()
    assert r2.state == "queued"                # still waiting on refill
    time.sleep(0.25)                           # 40 tok/s × 0.25 ≥ 8
    gw.step()
    assert r2.state == "dispatched"
    gw._drive_until([r2, free], timeout=10)
    assert r2.result() == [4, 5, 6, 7]


def test_gateway_queue_depth_pull_gauge():
    gw = _stub_gateway()
    hs = [gw.submit("m", _prompt(4), 1, priority="high"),
          gw.submit("m", _prompt(5), 1, priority="high"),
          gw.submit("m", _prompt(6), 1, priority="low")]
    rep = registry.report()
    assert rep['mx_gateway_queue_depth{priority="high"}']["value"] == 2.0
    assert rep['mx_gateway_queue_depth{priority="normal"}']["value"] == 0.0
    assert rep['mx_gateway_queue_depth{priority="low"}']["value"] == 1.0
    gw._drive_until(hs, timeout=10)
    rep = registry.report()
    assert rep['mx_gateway_queue_depth{priority="high"}']["value"] == 0.0


def test_gateway_step_fault_seam():
    from incubator_mxnet_tpu import fault

    gw = _stub_gateway()
    gw.submit("m", _prompt(4), 2)
    fault.configure_injection("gateway_step:1.0:0:1")
    try:
        with pytest.raises(fault.FaultInjected):
            gw.step()
    finally:
        fault.clear_injection()
    gw.step()                                  # limit=1: next step clean


def test_gateway_shutdown_drains_and_fails_queued():
    gw = _stub_gateway(max_slots=1)
    a = gw.submit("m", _prompt(4), 3)
    gw.step()
    b = gw.submit("m", _prompt(5), 3)          # still gateway-queued
    gw.shutdown(drain=True, timeout=10)
    assert a.state == "done" and a.result() == [4, 5, 6]
    assert b.state == "failed" and isinstance(b.error, EngineClosed)
    with pytest.raises(EngineClosed):
        gw.submit("m", _prompt(4), 2)
    # every page returned (prefix cache cleared at shutdown)
    assert gw._models["m"].slots.allocator.used_pages == 0


# ---------------------------------------------------------------------------
# observability: spans + flight recorder (quick)
# ---------------------------------------------------------------------------

@pytest.fixture()
def traced():
    tracing.enable()
    yield
    tracing.disable()
    tracing.reset()


def test_gateway_spans_join_request_trace(traced):
    """gateway.request → gateway.admit → serve.request are ONE trace per
    request: the engine segment's root span parents on the gateway's."""
    gw = _stub_gateway()
    h = gw.submit("m", _prompt(4), 2, tenant="acme", priority="high")
    gw._drive_until([h], timeout=10)
    spans = tracing.finished_spans(h.trace_id)
    names = [s.name for s in spans]
    assert {"gateway.request", "gateway.admit",
            "serve.request"} <= set(names)
    groot = next(s for s in spans if s.name == "gateway.request")
    sreq = next(s for s in spans if s.name == "serve.request")
    assert sreq.trace_id == groot.trace_id == h.trace_id
    assert groot.attrs["tenant"] == "acme"
    assert groot.attrs["priority"] == "high"
    assert groot.attrs["preemptions"] == 0


def test_gateway_preempted_trace_has_two_segments(traced):
    gw = _stub_gateway(max_slots=1)
    low = gw.submit("m", _prompt(4), 4, priority="low")
    gw.step()
    high = gw.submit("m", _prompt(6, seed=1), 2, priority="high")
    gw._drive_until([low, high], timeout=10)
    spans = tracing.finished_spans(low.trace_id)
    names = [s.name for s in spans]
    # two admits and two engine segments — the preemption is visible
    # in the request's own trace
    assert names.count("gateway.admit") == 2
    assert names.count("serve.request") == 2
    groot = next(s for s in spans if s.name == "gateway.request")
    assert groot.attrs["preemptions"] == 1


def test_flight_dump_carries_gateway_context(traced, tmp_path):
    gw = _stub_gateway()
    gw.submit("m", _prompt(4), 2, tenant="acme", priority="high")
    path = tracing.flight_dump("gwtest", path=str(tmp_path / "f.json"))
    with open(path) as f:
        payload = json.load(f)
    ctx = payload["context"]["gateway"]
    assert ctx["tiers"] == {"high": 1, "normal": 0, "low": 0}
    assert ctx["queued"][0]["tenant"] == "acme"
    assert ctx["queued"][0]["priority"] == "high"
    assert ctx["closed"] is False and ctx["preemptions_total"] == 0


# ---------------------------------------------------------------------------
# trace-replay acceptance gate on real compiled engines (ISSUE 9)
# ---------------------------------------------------------------------------

def _loadgen():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import loadgen
    finally:
        sys.path.pop(0)
    return loadgen


def _spicy_net(weight_seed):
    """Non-degenerate random weights, same recipe as test_serve.py."""
    mx.random.seed(11)
    m = gpt_tiny(vocab_size=VOCAB, max_length=64, dropout=0.0)
    m.initialize()
    r = onp.random.RandomState(weight_seed)
    for _name, p in m.collect_params().items():
        if p.shape and len(p.shape) >= 2:
            p.set_data(np.array(
                r.normal(0, 0.35, p.shape).astype("float32")))
    return m


def test_gateway_trace_replay_acceptance(tmp_path):
    """THE acceptance gate: two co-resident tiny GPTs behind one
    gateway, three tenants across three tiers on a recorded trace.
    Every request completes or fails loudly; the high tier's TTFT p99
    under contention stays within 1.5× its solo value; a deterministic
    contention episode preempts low-priority work that then FINISHES;
    per-engine program counts never move after warmup; and the high
    tier's `slo.gateway_ttft` error budget is compliant."""
    loadgen = _loadgen()
    reg = serve.ModelRegistry(total_pages=40)
    reg.add("gpt-a", _spicy_net(42), share=2.0, max_slots=2, max_len=64)
    reg.add("gpt-b", _spicy_net(43), share=1.0, max_slots=2, max_len=64)
    gw = serve.Gateway(reg, tenants={"acme": {"weight": 3.0},
                                     "beta": {"weight": 2.0},
                                     "crawl": {"weight": 1.0}})
    obj = slo.gateway_ttft("high", threshold_s=2.5, target=0.9,
                           name="gw_accept_high")
    try:
        # the shared page budget splits by share (2:1)
        assert (gw._models["gpt-a"].slots.allocator.usable_pages >
                gw._models["gpt-b"].slots.allocator.usable_pages)
        # warm every chunk bucket (16/32/64) + decode on both engines,
        # out of the measured window
        for name in ("gpt-a", "gpt-b"):
            for n in (5, 20, 40):
                gw.generate(name, _prompt(n, seed=n), 2)
        warm = gw.xla_program_counts()
        assert all(c >= 2 for c in warm.values())

        # solo baseline: the high tenant alone
        solo = loadgen.synth_trace(
            8, models={"gpt-a": 2.0, "gpt-b": 1.0},
            tenants={"acme": (1.0, "high")}, seed=5, duration_s=0.4,
            prompt_max=40, max_new_range=(3, 8))
        solo_rep = loadgen.replay(gw, solo, VOCAB, timeout=120.0)
        assert not solo_rep["failed"]
        assert solo_rep["completed"] == len(solo)
        solo_p99 = loadgen.percentile(
            solo_rep["per_tier"]["high"]["ttft"], 99)

        # contended run: 3 tenants / 3 tiers, bursty arrivals, via a
        # save/load JSONL roundtrip (the recorded-trace contract)
        events = loadgen.synth_trace(
            24, models={"gpt-a": 2.0, "gpt-b": 1.0},
            tenants={"acme": (1.5, "high"), "beta": (1.5, "normal"),
                     "crawl": (3.0, "low")},
            seed=7, duration_s=0.6, burst_factor=8.0, prompt_max=40,
            max_new_range=(3, 8))
        events = loadgen.load_trace(loadgen.save_trace(
            str(tmp_path / "trace.jsonl"), events))
        rep = loadgen.replay(gw, events, VOCAB, timeout=180.0)
        assert not rep["failed"], rep["failed"]
        assert rep["completed"] == len(events)
        hi_p99 = loadgen.percentile(rep["per_tier"]["high"]["ttft"], 99)
        assert hi_p99 <= 1.5 * solo_p99 + 0.1, (hi_p99, solo_p99)

        # deterministic contention: fill gpt-a's two slots with low-tier
        # work, then land a high request — a low MUST be preempted, keep
        # its pages/tokens, and still FINISH its full budget
        pre0 = gw.preemptions_total
        lows = [gw.submit("gpt-a", _prompt(6, seed=70 + i), 20,
                          tenant="crawl", priority="low")
                for i in range(2)]
        while not all(r.tokens for r in lows):
            gw.step()
        high = gw.submit("gpt-a", _prompt(8, seed=99), 4, tenant="acme",
                         priority="high")
        gw.step()
        assert gw.preemptions_total == pre0 + 1
        gw._drive_until(lows + [high], timeout=120.0)
        assert high.state == "done" and len(high.tokens) == 4
        assert [r for r in lows if r.preemptions]
        for r in lows:
            assert r.state == "done" and len(r.tokens) == 20

        # zero steady-state recompiles across replays AND preemption
        assert gw.xla_program_counts() == warm
        # the high tier's error budget survived the whole session
        res = obj.evaluate()
        assert res["compliance"] is not None and res["ok"], res
    finally:
        slo.tracker().remove("gw_accept_high")
        gw.shutdown(drain=False)
