"""serve.elastic — the elastic replica-set control plane (ISSUE 18).

All deterministic on CPU against the stub slot decoder (pure host
arithmetic, real PageAllocator/PrefixCache — same recipe as
test_gateway.py): scale-up spawns a WARMED replica and journals it,
scale-down drains (never below the floor) and retires once idle, a
replica killed mid-trace by the ``replica_crash`` chaos seam is
replaced with its in-flight work re-queued and ZERO failed requests, a
fault mid-spawn (``replica_spawn`` seam) rolls the fleet back to
exactly N, the page-budget funding gate fails LOUDLY, and the advisor
consume path acts on each recommendation exactly once.
"""
import numpy as onp
import pytest

from incubator_mxnet_tpu import serve
from incubator_mxnet_tpu.fault import injection
from incubator_mxnet_tpu.serve.elastic import (ReplicaScaleError,
                                               ReplicaSetController)
from incubator_mxnet_tpu.serve.engine import (PageAllocator,
                                              PagePoolExhausted,
                                              PrefixCache)
from incubator_mxnet_tpu.telemetry import registry

VOCAB = 97


@pytest.fixture(autouse=True)
def _clear_schedule():
    injection.clear_injection()
    yield
    injection.clear_injection()


class _StubSlots:
    """Paged-interface stand-in (same recipe as test_gateway.py): the
    final prefill chunk emits the prompt's length as the first token,
    decode increments — a request resumed after a replica crash from
    ``prompt + tokens`` must continue the same arithmetic run."""

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
        self.released = False
        self.programs = 2          # pretend both families are compiled

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
        return self.programs

    def release(self):
        self.released = True


def _elastic_gateway(max_replicas=3, min_replicas=1, **gw_kwargs):
    reg = serve.ModelRegistry()
    reg.add("m", _StubSlots())
    gw = serve.Gateway(reg, **gw_kwargs)
    ctl = gw.enable_elastic(
        factories={"m": lambda n_pages: _StubSlots(n_pages=n_pages)},
        min_replicas=min_replicas, max_replicas=max_replicas)
    return gw, ctl


def _prompt(n, seed=0):
    return onp.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(onp.int32)


def _drive(gw, handles, steps=400):
    for _ in range(steps):
        gw.step()
        if all(h.done for h in handles):
            return
    raise AssertionError(
        f"requests not done: {[h.state for h in handles]}")


def _counter(name):
    rep = registry.report()
    return rep.get(name, {}).get("value", 0) or 0


# ---------------------------------------------------------------------------
# scale-up: spawn, warm, publish
# ---------------------------------------------------------------------------

def test_scale_up_spawns_warmed_replica_and_journals():
    gw, ctl = _elastic_gateway()
    try:
        assert ctl.replica_count("m") == 1
        u0 = _counter('mx_elastic_scale_events_total{direction="up"}')
        added = ctl.scale_up("m")
        assert [r.label for r in added] == ["m#1"]
        assert ctl.replica_count("m") == 2
        # warmed before published: the program-count snapshot exists and
        # the warmup drove real traffic through the scheduler
        assert ctl.warm_programs["m#1"] == 2
        assert added[0].sched.idle          # warmup fully drained
        assert _counter('mx_elastic_scale_events_total{direction="up"}') \
            == u0 + 1
        assert [e["direction"] for e in ctl.events] == ["up"]
        # the new replica takes traffic
        hs = [gw.submit("m", _prompt(8, i), 4) for i in range(4)]
        _drive(gw, hs)
        assert {h.state for h in hs} == {"done"}
        assert any(len(r.live) or True for r in gw._models["m"].replicas)
    finally:
        gw.shutdown(drain=False)


def test_scale_up_respects_ceiling_and_reuses_draining():
    gw, ctl = _elastic_gateway(max_replicas=2)
    try:
        ctl.scale_up("m")
        assert ctl.scale_up("m") == []      # at the ceiling: no-op
        assert ctl.replica_count("m") == 2
        # a draining replica is un-drained before any spawn
        ctl.scale_down("m")
        assert ctl.replica_count("m", live_only=True) == 1
        added = ctl.scale_up("m")
        assert len(added) == 1 and not added[0].draining
        assert ctl.replica_count("m") == 2   # reused, not spawned
    finally:
        gw.shutdown(drain=False)


def test_replica_indices_never_reused():
    gw, ctl = _elastic_gateway(max_replicas=3)
    try:
        ctl.scale_up("m")                    # -> m#1
        ctl.scale_down("m")
        gw.step()                            # idle drain retires it
        assert ctl.replica_count("m") == 1
        added = ctl.scale_up("m")            # -> m#2, never m#1 again
        assert [r.label for r in added] == ["m#2"]
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# scale-down: drain, floor
# ---------------------------------------------------------------------------

def test_scale_down_drains_and_never_below_min():
    gw, ctl = _elastic_gateway()
    try:
        ctl.scale_up("m", 2)
        assert ctl.replica_count("m") == 3
        assert ctl.scale_down("m", 5) == 2   # floor-clamped
        assert ctl.replica_count("m", live_only=True) == 1
        assert ctl.scale_down("m") == 0      # at the floor already
        gw.step()                            # both idle: retired
        assert ctl.replica_count("m") == 1
        d = _counter('mx_elastic_scale_events_total{direction="down"}')
        assert d >= 2
    finally:
        gw.shutdown(drain=False)


def test_draining_replica_finishes_in_flight_then_retires():
    gw, ctl = _elastic_gateway()
    try:
        ctl.scale_up("m")
        hs = [gw.submit("m", _prompt(8, i), 6) for i in range(4)]
        for _ in range(3):
            gw.step()                        # dispatch across replicas
        victim = next(r for r in gw._models["m"].replicas if r.live)
        ctl.scale_down("m", 1)
        # the drained replica may be the busy one; either way nothing
        # fails and everything completes
        _drive(gw, hs)
        assert {h.state for h in hs} == {"done"}
        gw.step()
        assert ctl.replica_count("m") == 1
        assert victim.sched.idle or not victim.draining
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# replica death (chaos): replace + zero failed requests
# ---------------------------------------------------------------------------

def test_replica_crash_mid_trace_replaced_zero_failed():
    gw, ctl = _elastic_gateway()
    try:
        ctl.scale_up("m")
        hs = [gw.submit("m", _prompt(8, i), 8) for i in range(6)]
        for _ in range(4):
            gw.step()                        # in flight on both replicas
        r0 = _counter(
            'mx_elastic_scale_events_total{direction="replace"}')
        injection.configure_injection("replica_crash@1:1.0:0:1")
        gw.step()                            # the tick reaps and replaces
        injection.clear_injection()
        labels = [r.label for r in gw._models["m"].replicas]
        assert "m#1" not in labels           # the dead replica is gone
        assert "m#2" in labels               # replacement spawned+warmed
        assert _counter(
            'mx_elastic_scale_events_total{direction="replace"}') \
            == r0 + 1
        _drive(gw, hs)
        states = [h.state for h in hs]
        assert states.count("failed") == 0, states
        assert {h.state for h in hs} == {"done"}
        # resumed arithmetic stayed continuous: first token is the
        # prompt length, then +1 per decode — crash resume included
        for h in hs:
            toks = h.result()
            assert toks == list(range(toks[0], toks[0] + len(toks)))
    finally:
        gw.shutdown(drain=False)


def test_crash_below_min_heals_next_tick_even_if_spawn_fails_once():
    gw, ctl = _elastic_gateway()
    try:
        # kill the only replica while ALSO failing the replacement spawn:
        # the fleet degrades to zero, then heals on a later tick
        injection.configure_injection(
            "replica_crash@0:1.0:0:1,replica_spawn:1.0:0:1")
        gw.step()
        injection.clear_injection()
        assert ctl.replica_count("m") in (0, 1)
        gw.step()                            # heal path retries
        assert ctl.replica_count("m") == 1
        hs = [gw.submit("m", _prompt(8, i), 4) for i in range(2)]
        _drive(gw, hs)
        assert {h.state for h in hs} == {"done"}
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# failed spawn: rollback to N
# ---------------------------------------------------------------------------

def test_spawn_fault_rolls_back_to_n_replicas():
    gw, ctl = _elastic_gateway()
    try:
        injection.configure_injection("replica_spawn:1.0:0:1")
        with pytest.raises(injection.FaultInjected):
            ctl.scale_up("m")
        injection.clear_injection()
        # fleet unchanged, no half-registered replica, engine released
        assert ctl.replica_count("m") == 1
        assert [r.label for r in gw._models["m"].replicas] == ["m"]
        assert "m#1" not in ctl.warm_programs
        # the next spawn works and does NOT reuse the burned index
        added = ctl.scale_up("m")
        assert [r.label for r in added] == ["m#1"]
    finally:
        gw.shutdown(drain=False)


def test_warmup_failure_is_rolled_back_and_loud():
    gw, ctl = _elastic_gateway()

    class _BadDecode(_StubSlots):
        def decode_step(self, *a, **k):
            raise RuntimeError("device wedged")

    ctl._factories["m"] = lambda n_pages: _BadDecode(n_pages=n_pages)
    try:
        with pytest.raises(ReplicaScaleError, match="warmup"):
            ctl.scale_up("m")
        assert ctl.replica_count("m") == 1
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# page-budget funding gate
# ---------------------------------------------------------------------------

def test_rebalance_pages_funding_gate_is_loud():
    reg = serve.ModelRegistry(total_pages=24)
    reg.add("m", _StubSlots())
    assert reg.rebalance_pages("m", 2) == 12
    assert reg.rebalance_pages("m", 6) == 4
    with pytest.raises(PagePoolExhausted, match="replica"):
        reg.rebalance_pages("m", 7)          # 24/7 < 4 pages: unfunded
    with pytest.raises(ValueError):
        reg.rebalance_pages("ghost", 2)
    # an unbudgeted registry never constrains (None = no shared pool)
    assert serve.ModelRegistry().rebalance_pages is not None


def test_unfunded_scale_up_leaves_fleet_intact():
    reg = serve.ModelRegistry(total_pages=16)
    reg.add("m", _StubSlots(n_pages=8))
    gw = serve.Gateway(reg)
    ctl = gw.enable_elastic(
        factories={"m": lambda n_pages: _StubSlots(n_pages=n_pages)},
        max_replicas=8)
    try:
        ctl.scale_up("m")                    # 16/2 = 8: funded
        ctl.scale_up("m")                    # 16/3 = 5: funded
        ctl.scale_up("m")                    # 16/4 = 4: funded
        with pytest.raises(PagePoolExhausted):
            ctl.scale_up("m")                # 16/5 < 4: LOUD, no spawn
        assert ctl.replica_count("m") == 4
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# advisor consumption + telemetry
# ---------------------------------------------------------------------------

def test_controller_consumes_each_recommendation_once():
    gw, ctl = _elastic_gateway()
    try:
        adv = gw._advisors.get("m")
        if adv is None:
            from incubator_mxnet_tpu.serve.advisor import AutoscaleAdvisor

            adv = gw._advisors["m"] = AutoscaleAdvisor("m")
        rec = {"t": 10.0, "action": "scale_up", "model": "m", "n": 1,
               "reason": "test", "evidence": {}}
        adv._log.append(rec)
        assert ctl.tick(now=11.0) == 1
        assert ctl.replica_count("m") == 2
        # the same recommendation is never acted on twice
        assert ctl.tick(now=12.0) == 0
        assert ctl.replica_count("m") == 2
        adv._log.append(dict(rec, t=20.0, action="scale_down"))
        ctl.tick(now=21.0)
        assert ctl.replica_count("m", live_only=True) == 1
    finally:
        gw.shutdown(drain=False)


def test_mx_serve_replicas_gauge_tracks_fleet():
    gw, ctl = _elastic_gateway()
    try:
        assert _counter('mx_serve_replicas{model="m"}') == 1
        ctl.scale_up("m")
        assert _counter('mx_serve_replicas{model="m"}') == 2
        ctl.scale_down("m")
        gw.step()
        assert _counter('mx_serve_replicas{model="m"}') == 1
    finally:
        gw.shutdown(drain=False)


def test_elastic_serve_knob_arms_controller(monkeypatch):
    monkeypatch.setenv("MXNET_ELASTIC_SERVE", "1")
    monkeypatch.setenv("MXNET_ELASTIC_MIN_REPLICAS", "1")
    monkeypatch.setenv("MXNET_ELASTIC_MAX_REPLICAS", "4")
    reg = serve.ModelRegistry()
    reg.add("m", _StubSlots())
    gw = serve.Gateway(reg)
    try:
        assert isinstance(gw._elastic, ReplicaSetController)
        assert gw._elastic.min_replicas == 1
        assert gw._elastic.max_replicas == 4
    finally:
        gw.shutdown(drain=False)


def test_prebuilt_model_without_factory_raises_clear_error():
    reg = serve.ModelRegistry()
    reg.add("m", _StubSlots())
    gw = serve.Gateway(reg)
    ctl = gw.enable_elastic()                # no factories
    try:
        with pytest.raises(ValueError, match="factories"):
            ctl.scale_up("m")
        assert ctl.replica_count("m") == 1
    finally:
        gw.shutdown(drain=False)
