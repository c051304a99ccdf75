"""telemetry.anatomy — the serving goodput observatory (ISSUE 20).

Stub-gateway tests (pure host arithmetic over REAL
PageAllocator/PrefixCache — the test_gateway.py recipe) gate the
sum-to-wall invariant at <=2% residual across the four request shapes
(plain, preempted, disagg-migrated, spec-decode), the tail-sampling
truth table (a flagged request is ALWAYS archived, normal traffic is
sampled), the disarmed dead branch (begin() returns None and every
seam no-ops) with the literal off-path probe under 3% of a decode
step, role-aware advisor refinement naming the residency series, and
the elastic consume path pinning the spawned replica's role. The
real-engine test is the acceptance gate: on a disaggregated
prefill/decode pod the migrated request's ``handoff_migration`` state
is nonzero, its states sum to its measured wall within 2%, and the
decode replica's residency is decode-dominated.
"""
import gc
import json
import os
import sys
import time

import numpy as onp
import pytest

from incubator_mxnet_tpu import serve
from incubator_mxnet_tpu.fault import injection
from incubator_mxnet_tpu.serve.advisor import (RESIDENCY_SERIES,
                                               AutoscaleAdvisor)
from incubator_mxnet_tpu.serve.engine import PageAllocator, PrefixCache
from incubator_mxnet_tpu.telemetry import (anatomy, burnrate, capacity,
                                           registry, timeseries)

VOCAB = 97
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _armed_anatomy():
    injection.clear_injection()
    # a gateway an earlier test file left to the garbage collector still
    # answers `mx_gateway_queue_depth` (a pull gauge over its queues) in
    # place of the value a test here sets
    gc.collect()
    registry.reset()
    anatomy.reset()
    anatomy.enable()
    anatomy.set_sample(1.0)          # archive everything by default
    yield
    anatomy.disable()
    anatomy.reset()
    anatomy.set_sample(0.05)
    timeseries.disable()
    timeseries.reset()
    burnrate.clear()
    injection.clear_injection()


class _StubSlots:
    """Paged-interface stand-in (same recipe as test_gateway.py):
    final prefill chunk emits the prompt's length, decode increments."""

    def __init__(self, max_slots=2, max_len=64, page_tokens=16,
                 prefill_chunk=64, n_pages=None):
        self.max_slots = max_slots
        self.max_len = max_len
        self.page_tokens = page_tokens
        self.prefill_chunk = prefill_chunk
        pages_per_slot = -(-max_len // page_tokens)
        self.allocator = PageAllocator(
            n_pages if n_pages is not None
            else max_slots * pages_per_slot + 1, page_tokens)
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


class _SpecStubSlots(_StubSlots):
    """Spec-decode stand-in: drafts the correct next token then a wrong
    one, so every round accepts 1 of k=2 — half the round's decode wall
    is carved to ``spec_overhead`` while the invariant still holds."""

    spec_k = 2
    draft_kind = "ngram"

    def spec_propose(self, seqs):
        drafts = onp.zeros((self.max_slots, self.spec_k), onp.int32)
        for s, seq in enumerate(seqs):
            if seq is not None:
                drafts[s, 0] = int(seq[-1]) + 1        # accepted
                drafts[s, 1] = 0                       # rejected
        return drafts

    def spec_verify_step(self, last, drafts, pos, active, limit):
        k = self.spec_k
        out = onp.zeros((self.max_slots, k + 1), onp.int32)
        for s in range(self.max_slots):
            for i in range(k + 1):
                out[s, i] = int(last[s]) + 1 + i
        return out

    def spec_count(self, k, accepted):
        pass


def _prompt(n, seed=0):
    return onp.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(onp.int32)


def _stub_gateway(max_slots=2, slots_cls=_StubSlots, **gw_kwargs):
    reg = serve.ModelRegistry()
    reg.add("m", slots_cls(max_slots=max_slots))
    return serve.Gateway(reg, **gw_kwargs)


def _disagg_gateway(n_prefill=1, n_decode=1):
    stubs = ([_StubSlots() for _ in range(n_prefill)]
             + [_StubSlots() for _ in range(n_decode)])
    reg = serve.ModelRegistry()
    reg.add("m", stubs, prefill_replicas=n_prefill,
            decode_replicas=n_decode)
    return serve.Gateway(reg), stubs


def _drive(gw, handles, steps=400):
    for _ in range(steps):
        gw.step()
        if all(h.done for h in handles):
            return
    raise AssertionError(
        f"requests not done: {[h.state for h in handles]}")


def _gate(rec, tol=0.02):
    """The sum-to-wall invariant: every second of the request's wall is
    attributed to exactly one anatomy state."""
    assert rec is not None
    assert rec.wall_s > 0
    assert abs(rec.residual_s) <= tol * rec.wall_s, (
        rec.residual_s, rec.wall_s, rec.states)
    assert all(v >= 0.0 for v in rec.states.values()), rec.states


# ---------------------------------------------------------------------------
# sum-to-wall across the four request shapes (stub gateway)
# ---------------------------------------------------------------------------

def test_plain_requests_sum_to_wall():
    gw = _stub_gateway()
    try:
        hs = [gw.submit("m", _prompt(4 + i, seed=i), 4)
              for i in range(3)]
        _drive(gw, hs)
    finally:
        gw.shutdown(drain=False)
    for h in hs:
        rec = h._anatomy
        _gate(rec)
        assert rec.outcome == "ok"
        assert rec.states["decode_compute"] > 0.0
        assert rec.states["preempted"] == 0.0
        assert not rec.flags
    rep = registry.report()
    assert rep['mx_request_anatomy_requests_total{outcome="ok"}'][
        "value"] == 3
    # the per-state counter mirrors the per-request ledgers
    total = sum(rep[f'mx_request_anatomy_seconds_total{{state="{s}"}}'][
        "value"] for s in anatomy.STATES
        if f'mx_request_anatomy_seconds_total{{state="{s}"}}' in rep)
    assert total == pytest.approx(sum(r._anatomy.wall_s for r in hs),
                                  rel=0.02)


def test_preempted_request_charges_requeued_wall():
    """The satellite fix: wall spent re-queued after a preemption lands
    in the ``preempted`` state and the victim still sums to wall."""
    gw = _stub_gateway(max_slots=1)
    try:
        low = gw.submit("m", _prompt(4), 8, tenant="crawl",
                        priority="low")
        gw.step()
        assert low.state == "dispatched"
        high = gw.submit("m", _prompt(6, seed=1), 3, tenant="acme",
                         priority="high")
        gw.step()
        assert low.state == "queued" and low.preemptions == 1
        _drive(gw, [low, high])
    finally:
        gw.shutdown(drain=False)
    rec = low._anatomy
    _gate(rec)
    assert "preempted" in rec.flags
    assert rec.states["preempted"] > 0.0
    assert rec.resumes == 1
    _gate(high._anatomy)
    assert "preempted" not in high._anatomy.flags


def test_disagg_migrated_request_sums_to_wall():
    gw, _stubs = _disagg_gateway()
    try:
        hs = [gw.submit("m", _prompt(5 + i, seed=i), 4)
              for i in range(2)]
        _drive(gw, hs)
    finally:
        gw.shutdown(drain=False)
    for h in hs:
        rec = h._anatomy
        _gate(rec)
        assert "migrated" in rec.flags
        assert rec.states["handoff_migration"] > 0.0
    # both shapes of the archive keep a migrated request
    assert {r["id"] for r in anatomy.archive()} >= {h.id for h in hs}


def test_spec_decode_round_carves_overhead():
    gw = _stub_gateway(slots_cls=_SpecStubSlots)
    try:
        h = gw.submit("m", _prompt(4), 6)
        _drive(gw, [h])
    finally:
        gw.shutdown(drain=False)
    rec = h._anatomy
    _gate(rec)
    # every round rejected one of two drafts: waste was carved out of
    # ambient decode_compute, not double-counted on top of it
    assert rec.states["spec_overhead"] > 0.0
    assert rec.states["decode_compute"] >= 0.0


# ---------------------------------------------------------------------------
# tail-sampling truth table + archive bound
# ---------------------------------------------------------------------------

def _fake_request(i, now, outcome="ok", flag=None):
    rec = anatomy.begin(i, "t", "m", "normal", now)
    rec.dispatched(now + 0.01, "m#0")
    rec.prefill_done(now + 0.02)
    if flag is not None:
        rec.requeued(now + 0.03, flag)
        rec.dispatched(now + 0.04, "m#0")
        rec.prefill_done(now + 0.05)
    anatomy.complete(rec, now + 0.1, outcome)
    return rec


def test_tail_sampling_truth_table():
    anatomy.set_sample(0.0)          # drop ALL normal traffic
    _fake_request(0, 0.0)                                  # normal
    _fake_request(1, 1.0, outcome="expired")               # SLO violator
    _fake_request(2, 2.0, flag="preempted")
    _fake_request(3, 3.0, flag="migration_fallback")
    _fake_request(4, 4.0, flag="crash_resume")
    _fake_request(5, 5.0)                                  # normal
    kept = {r["id"] for r in anatomy.archive()}
    assert kept == {1, 2, 3, 4}      # flagged ALWAYS kept, normal never
    # rate 1.0 keeps every normal request
    anatomy.set_sample(1.0)
    _fake_request(6, 6.0)
    assert 6 in {r["id"] for r in anatomy.archive()}
    # rate 0.5 keeps every second NORMAL request, deterministically
    anatomy.reset()
    anatomy.set_sample(0.5)
    for i in range(6):
        _fake_request(i, float(i))
    kept = sorted(r["id"] for r in anatomy.archive())
    assert len(kept) == 3


def test_archive_ring_is_bounded():
    anatomy.set_ring(4)
    try:
        for i in range(10):
            _fake_request(i, float(i), flag="preempted")
        tail = anatomy.archive()
        assert len(tail) == 4
        assert [r["id"] for r in tail] == [6, 7, 8, 9]
    finally:
        anatomy.set_ring(256)


def test_report_and_waterfall_render():
    _fake_request(0, 0.0, flag="preempted")
    anatomy.charge_replica("m#0", "prefill", "prefill", 0.5, now=1.0)
    rep = anatomy.report(now=2.0)
    assert rep["requests_completed"] == 1
    assert rep["replicas"]["m#0"]["role"] == "prefill"
    art = anatomy.format_waterfall(
        next(iter(anatomy.archive())))
    assert "preempted" in art or "P" in art


# ---------------------------------------------------------------------------
# disarmed dead branch + the off-path probe bound
# ---------------------------------------------------------------------------

def test_disarmed_begin_returns_none_and_seams_noop():
    anatomy.disable()
    assert anatomy.begin(0, "t", "m", "normal", 0.0) is None
    anatomy.charge_replica("m#0", "decode", "decode", 1.0, now=1.0)
    assert anatomy.residency_report(now=2.0) == {}
    anatomy.complete(None, 1.0, "ok")        # None record: no-op
    assert anatomy.archive() == []
    # a full gateway run with anatomy off leaves records unset
    gw = _stub_gateway()
    try:
        h = gw.submit("m", _prompt(4), 3)
        _drive(gw, [h])
    finally:
        gw.shutdown(drain=False)
    assert h._anatomy is None
    assert h.result() == [4, 5, 6]


def test_off_path_seams_read_no_clock_and_touch_no_ledger(monkeypatch,
                                                          count_clock_reads):
    """Disarmed, the scheduler's seams are one module-flag check each: a
    stub scheduler's steps call them with the step clock's stamps, and they
    read no clock, charge no replica and leave no series. (Structural: a
    CPU run gives counts, not speeds.)"""
    anatomy.disable()
    capacity.disable()
    clock = count_clock_reads(anatomy)
    calls = {"prefill": 0, "decode": 0}
    real = (anatomy.on_prefill_chunk, anatomy.on_decode_step)

    def on_prefill_chunk(sched, req, t0, t1, now=None):
        calls["prefill"] += 1
        assert t1 >= t0                    # the step's stamps, handed in
        return real[0](sched, req, t0, t1, now=now)

    def on_decode_step(sched, t0, t1, now=None):
        calls["decode"] += 1
        assert t1 >= t0
        assert real[1](sched, t0, t1, now=now) == 0.0
        return 0.0

    monkeypatch.setattr(anatomy, "on_prefill_chunk", on_prefill_chunk)
    monkeypatch.setattr(anatomy, "on_decode_step", on_decode_step)
    from incubator_mxnet_tpu.serve.scheduler import Scheduler

    sched = Scheduler(_StubSlots(), max_queue=4)
    sched.capacity_model = "off-path"         # the replica label it would get
    req = sched.submit(_prompt(4), 3)
    while not req.done:
        sched.step()
    assert calls == {"prefill": 1, "decode": 2} and clock.reads == 0
    assert req.anatomy is None and anatomy.residency_report() == {}
    assert not [k for k in registry.report()
                if k.startswith("mx_replica_residency") and "off-path" in k]


# ---------------------------------------------------------------------------
# replica residency + role-aware advisor + elastic consume
# ---------------------------------------------------------------------------

def test_residency_counters_and_fractions():
    anatomy.charge_replica("m#0", "prefill", "prefill", 8.0, now=9.0)
    anatomy.charge_replica("m#1", "decode", "decode", 2.0, now=4.0)
    anatomy.charge_replica("m#1", "decode", "migration", 0.5, now=4.5)
    rep = anatomy.residency_report(now=10.0)
    r0, r1 = rep["m#0"], rep["m#1"]
    assert r0["frac"]["prefill"] == pytest.approx(8.0 / 9.0)
    assert r0["frac"]["idle"] == pytest.approx(1.0 / 9.0)
    # idle is the unexplained remainder of the replica's wall
    assert r1["frac"]["idle"] == pytest.approx(
        1.0 - r1["frac"]["decode"] - r1["frac"]["migration"])
    c = registry.report()[
        'mx_replica_residency_seconds_total'
        '{replica="m#0",role="prefill",state="prefill"}']
    assert c["value"] == pytest.approx(8.0)


def test_advisor_scale_up_refined_by_role_residency():
    """A plain scale_up on a disaggregated pod becomes
    ``scale_up_prefill`` when the prefill-role replicas are markedly
    busier — and the reason names the residency series."""
    timeseries.enable(interval_s=1.0, samples=64, thread=False)
    adv = AutoscaleAdvisor("m", fast_window_s=8.0)
    registry.gauge("mx_serve_slot_occupancy", "occ").set(0.95)
    registry.gauge("mx_gateway_queue_depth", "depth",
                   labels={"priority": "normal"}).set(4)
    for t in range(1, 9):
        timeseries.sample_now(now=float(t))
    # prefill side pinned busy for its whole wall, decode side 25% busy
    anatomy.charge_replica("m#0", "prefill", "prefill", 7.0, now=8.0)
    anatomy.charge_replica("m#1", "decode", "decode", 2.0, now=3.0)
    rec = adv.evaluate(now=8.0)
    assert rec["action"] == "scale_up_prefill"
    assert RESIDENCY_SERIES in rec["reason"]
    assert rec["evidence"][f"{RESIDENCY_SERIES} busy[prefill]"] \
        == pytest.approx(1.0)
    # homogeneous pod (no decode-role rows): the plain action survives
    anatomy.reset()
    anatomy.charge_replica("m#0", "both", "decode", 1.0, now=8.0)
    rec = adv.evaluate(now=8.5)
    assert rec["action"] == "scale_up"
    assert RESIDENCY_SERIES not in rec["reason"]


def test_elastic_consumes_role_action_and_pins_role():
    gw, stubs = _disagg_gateway()
    try:
        ctl = gw.enable_elastic(
            factories={"m": lambda n_pages: _StubSlots(n_pages=n_pages)},
            min_replicas=2, max_replicas=4)
        adv = gw._advisors.get("m")
        if adv is None:
            adv = gw._advisors["m"] = AutoscaleAdvisor("m")
        adv._log.append({"t": 10.0, "action": "scale_up_decode",
                         "model": "m", "n": 1, "reason": "test",
                         "evidence": {}})
        assert ctl.tick(now=11.0) == 1
        reps = gw._models["m"].replicas
        assert reps[-1].role == "decode"
        # acted on exactly once
        assert ctl.tick(now=12.0) == 0
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# real engines: the acceptance gate
# ---------------------------------------------------------------------------

def test_real_engine_disagg_anatomy_gate():
    """On a real disaggregated pod the migrated request's anatomy has a
    nonzero ``handoff_migration`` state, its states sum to its measured
    wall within 2%, and the decode replica's residency is
    decode-dominated among active states."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import np
    from incubator_mxnet_tpu.models.gpt import gpt_tiny

    mx.random.seed(11)
    net = gpt_tiny(vocab_size=VOCAB, max_length=64, dropout=0.0)
    net.initialize()
    reg = serve.ModelRegistry(total_pages=40)
    reg.add("gpt", net, prefill_replicas=1, decode_replicas=1,
            max_slots=2, max_len=64)
    gw = serve.Gateway(reg)
    try:
        hs = []
        for i, (n, new) in enumerate([(21, 6), (7, 8)]):
            h = gw.submit("gpt", _prompt(n, seed=1 + i), new)
            gw._drive_until([h], timeout=120.0)
            hs.append(h)
        for h in hs:
            assert h.replica == "gpt#1"       # finished on decode side
            rec = h._anatomy
            _gate(rec)
            assert "migrated" in rec.flags
            assert rec.states["handoff_migration"] > 0.0
            assert rec.states["prefill_compute"] > 0.0
            assert rec.states["decode_compute"] > 0.0
        res = anatomy.residency_report()
        dec = res["gpt#1"]
        assert dec["role"] == "decode"
        active = {s: dec["states"].get(s, 0.0)
                  for s in ("prefill", "decode", "migration", "warmup")}
        assert active["decode"] == max(active.values())
        assert active["prefill"] == 0.0
        # the prefill replica never decoded
        assert res["gpt#0"]["states"].get("decode", 0.0) == 0.0
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# reqscope --demo reproducibility (satellite: committed fixture)
# ---------------------------------------------------------------------------

def test_reqscope_demo_is_reproducible_and_committed():
    # The demo drives a virtual clock, so the report is exactly
    # deterministic — the committed fixture must match byte-for-byte
    # (modulo JSON round-tripping of floats, which is itself exact).
    from incubator_mxnet_tpu.telemetry import capacity
    capacity.disable()
    capacity.reset()
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import reqscope
    finally:
        sys.path.pop(0)

    rep = reqscope.run_demo()
    assert rep["mode"] == "reqscope-demo"
    assert rep["virtual_clock"] is True
    assert rep["requests_completed"] == 12
    assert rep["archive_depth"] == {"tail": 5, "sampled": 3}

    archive = rep["archive"]
    by_id = {r["id"]: r for r in archive}
    # every flagged request survives tail sampling
    assert "preempted" in by_id[7]["flags"]
    assert "migrated" in by_id[8]["flags"]
    assert "migration_fallback" in by_id[9]["flags"]
    assert "slo_violation" in by_id[10]["flags"]
    assert "crash_resume" in by_id[11]["flags"]
    # 3 of 7 normals kept at sample=0.5 (deterministic stride)
    normal = [r["id"] for r in archive if not r["flags"]]
    assert sorted(normal) == [1, 3, 5]

    with open(os.path.join(REPO, "benchmark", "reqscope_demo.json")) as f:
        committed = json.load(f)
    fresh = json.loads(json.dumps(rep, sort_keys=True))
    assert fresh == committed

    # the rendered report is byte-stable too
    text_fresh = reqscope.format_report(rep)
    text_committed = reqscope.format_report(committed)
    assert text_fresh == text_committed
    assert "replica residency" in text_fresh
    assert "gpt-demo#0" in text_fresh
