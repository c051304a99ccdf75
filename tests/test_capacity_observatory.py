"""Capacity observatory (ISSUE 17): time-series ring histories and
windowed queries, Prometheus exposition grammar round-trip,
multi-window burn-rate alerts with hysteresis, the per-tenant cost
ledger's 5 % wall audit through the real serving seams, the observe-only
autoscale advisor over a seeded diurnal trace, and the disarmed-path
dead-branch gate."""
import os
import sys
import time

import numpy as onp
import pytest

from incubator_mxnet_tpu import serve
from incubator_mxnet_tpu.serve.advisor import AutoscaleAdvisor
from incubator_mxnet_tpu.serve.engine import (PageAllocator, PrefixCache)
from incubator_mxnet_tpu.telemetry import (burnrate, capacity, registry,
                                           timeseries)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = 97


def _tools():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import capwatch
        import loadgen
    finally:
        sys.path.pop(0)
    return capwatch, loadgen


@pytest.fixture(autouse=True)
def _clean_observatory():
    yield
    timeseries.disable()
    timeseries.reset()
    burnrate.clear()
    capacity.disable()
    capacity.reset()
    registry.reset()


# ---------------------------------------------------------------------------
# time-series layer: rings and windowed queries
# ---------------------------------------------------------------------------

def _series(name, values, dt=1.0):
    """Build a history for gauge `name` on a virtual clock; returns the
    series key and the final virtual timestamp."""
    g = registry.gauge(name, "test series")
    t = 0.0
    for v in values:
        g.set(v)
        timeseries.sample_now(now=t)
        t += dt
    return name, t - dt


def test_ring_wraparound_keeps_newest():
    timeseries.enable(interval_s=1.0, samples=8, thread=False)
    key, _t = _series("t_wrap", range(20))
    hist = timeseries.history(key)
    # capacity-bounded: exactly the newest 8, oldest→newest, timestamps
    # strictly increasing across the wrap seam
    assert [v for _t, v in hist] == [12, 13, 14, 15, 16, 17, 18, 19]
    ts = [t for t, _v in hist]
    assert ts == sorted(ts) and len(set(ts)) == len(ts)
    assert timeseries.last(key) == (19.0, 19.0)


def test_rate_counter_reset_aware():
    timeseries.enable(interval_s=1.0, samples=64, thread=False)
    c = registry.counter("t_rst_total", "test counter")
    vals = [0, 10, 20, 5, 15]       # process restart between 20 and 5
    t = 0.0
    for v in vals:
        c._cell()[0] = v             # set absolute value (restart sim)
        timeseries.sample_now(now=t)
        t += 1.0
    # prometheus convention: a drop restarts from zero, so the post-
    # reset reading IS the increase: 10+10+5+10 = 35 over 4 s
    r = timeseries.rate("t_rst_total", window_s=10.0, now=4.0)
    assert r == pytest.approx(35.0 / 4.0)
    # plain delta is last-first (reset-blind by contract)
    assert timeseries.delta("t_rst_total", 10.0, now=4.0) == \
        pytest.approx(15.0)


def test_rate_needs_two_samples_and_known_series():
    timeseries.enable(interval_s=1.0, samples=8, thread=False)
    assert timeseries.rate("t_nope", 10.0) is None
    _series("t_one", [5])
    assert timeseries.rate("t_one", 10.0, now=0.0) is None
    assert timeseries.last("t_one") == (0.0, 5.0)


def test_percentile_over_time_matches_numpy():
    timeseries.enable(interval_s=1.0, samples=128, thread=False)
    rng = onp.random.RandomState(7)
    vals = rng.uniform(-10, 10, 101)
    key, t_end = _series("t_pct", vals)
    for q in (0, 10, 25, 50, 75, 90, 99, 100):
        got = timeseries.percentile_over_time(key, q, 1000.0, now=t_end)
        want = float(onp.percentile(vals, q, method="nearest"))
        assert got == pytest.approx(want), q


def test_window_frac_and_avg():
    timeseries.enable(interval_s=1.0, samples=64, thread=False)
    key, t_end = _series("t_frac", [0, 1, 1, 1, 0])
    assert timeseries.avg_over_time(key, 100.0, now=t_end) == \
        pytest.approx(0.6)
    assert timeseries.window_frac(key, 100.0, lambda v: v > 0.5,
                                  now=t_end) == pytest.approx(0.6)
    # window narrower than history: only the newest samples count
    assert timeseries.window_frac(key, 1.5, lambda v: v > 0.5,
                                  now=t_end) == pytest.approx(0.5)


def test_histogram_series_expand_to_count_and_sum():
    timeseries.enable(interval_s=1.0, samples=16, thread=False)
    h = registry.histogram("t_cap_lat_seconds", "test latencies")
    h.observe(0.1)
    timeseries.sample_now(now=0.0)
    h.observe(0.3)
    h.observe(0.5)
    timeseries.sample_now(now=1.0)
    assert timeseries.delta("t_cap_lat_seconds:count", 10.0, now=1.0) == 2
    assert timeseries.delta("t_cap_lat_seconds:sum", 10.0, now=1.0) == \
        pytest.approx(0.8)


def test_timeseries_sampler_thread_and_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_TS_INTERVAL", "0.01")
    monkeypatch.setenv("MXNET_TS_SAMPLES", "32")
    registry.counter("t_thr_total", "test").inc()
    timeseries.enable()
    assert timeseries.is_enabled()
    deadline = time.monotonic() + 5.0
    while timeseries.sample_count() < 3:
        assert time.monotonic() < deadline, "sampler thread never ticked"
        time.sleep(0.01)
    timeseries.disable()
    # rings stay queryable after disable (post-run reads); reset drops
    assert timeseries.history("t_thr_total")
    timeseries.reset()
    assert timeseries.history("t_thr_total") is None


def test_timeseries_off_by_default_is_inert():
    assert not timeseries.is_enabled()
    assert timeseries.sample_count() == 0
    assert timeseries.series_names() == []


# ---------------------------------------------------------------------------
# Prometheus exposition: grammar round-trip (satellite 1)
# ---------------------------------------------------------------------------

def test_exposition_grammar_round_trip():
    capwatch, _ = _tools()
    registry.counter("t_rt_total", "a counter", labels={"k": "v"}).inc(3)
    registry.counter("t_rt_total", "a counter",
                     labels={"k": "w\"x\\y\nz"}).inc(2)
    registry.gauge("t_rt_gauge", "a gauge").set(1.5)
    h = registry.histogram("t_rt_seconds", "a histogram")
    for v in (0.002, 0.02, 0.2, 2.0):
        h.observe(v)
    registry.register_pull_gauge("t_rt_pull", lambda: 7.0,
                                 "a pull gauge", labels={"p": "q"})
    text = registry.exposition()

    # every non-comment line parses under the exposition grammar
    samples = capwatch.parse_exposition(text)
    by_key = {}
    for name, labels, value in samples:
        by_key[(name, tuple(sorted(labels.items())))] = value
    assert by_key[("t_rt_total", (("k", "v"),))] == 3
    # escaped label value round-trips to the original string
    assert by_key[("t_rt_total", (("k", 'w"x\\y\nz'),))] == 2
    assert by_key[("t_rt_gauge", ())] == 1.5
    assert by_key[("t_rt_pull", (("p", "q"),))] == 7.0

    # HELP/TYPE discipline: every sample's family announced once, with
    # the right TYPE, contiguously (prometheus requires one block per
    # family)
    lines = text.splitlines()
    types = {}
    for ln in lines:
        if ln.startswith("# TYPE "):
            _h, _t, fam, kind = ln.split(" ", 3)
            assert fam not in types, f"family {fam} announced twice"
            types[fam] = kind
    assert types["t_rt_total"] == "counter"
    assert types["t_rt_gauge"] == "gauge"
    assert types["t_rt_seconds"] == "histogram"
    assert types["t_rt_pull"] == "gauge"

    # histogram exposition: cumulative buckets ending at +Inf == count,
    # and sum/count samples present
    buckets = [(labels["le"], value) for name, labels, value in samples
               if name == "t_rt_seconds_bucket"]
    assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 4
    counts = [v for _le, v in buckets]
    assert counts == sorted(counts), "buckets must be cumulative"
    assert by_key[("t_rt_seconds_count", ())] == 4
    assert by_key[("t_rt_seconds_sum", ())] == pytest.approx(2.222)

    # family blocks are contiguous: HELP/TYPE/rows never interleave
    fam_of = []
    for ln in lines:
        if not ln or ln.startswith("#"):
            continue
        name = ln.split("{", 1)[0].split(" ", 1)[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in types:
                name = name[:-len(suffix)]
        fam_of.append(name)
    seen, prev = set(), None
    for fam in fam_of:
        if fam != prev:
            assert fam not in seen, f"family {fam} rows not contiguous"
            seen.add(fam)
            prev = fam


# ---------------------------------------------------------------------------
# burn-rate alerts: fast/slow truth table + hysteresis (satellite 4)
# ---------------------------------------------------------------------------

def _burn_series(slo="t"):
    return registry.gauge("mx_slo_error_budget_burn",
                          "error-budget burn", labels={"slo": slo})


def _feed(g, value, t):
    g.set(value)
    timeseries.sample_now(now=t)


def test_burn_alert_fast_window_catches_flash_burst():
    timeseries.enable(interval_s=1.0, samples=512, thread=False)
    g = _burn_series()
    a = burnrate.BurnRateAlert("a", "t", windows=((60.0, 10.0),
                                                 (600.0, 2.0)))
    t = 0.0
    for _ in range(60):              # quiet hour-fragment
        _feed(g, 0.5, t)
        a.evaluate(now=t)
        t += 1.0
    assert not a.firing
    for _ in range(70):              # flash burst: fast window trips
        _feed(g, 25.0, t)
        a.evaluate(now=t)
        t += 1.0
    assert a.firing
    assert registry.gauge("mx_alert_firing",
                          labels={"alert": "a"}).value == 1


def test_burn_alert_slow_window_catches_slow_leak():
    timeseries.enable(interval_s=1.0, samples=2048, thread=False)
    g = _burn_series()
    # burn 3.0 sustained: below the fast 10x factor, above the slow 2x
    a = burnrate.BurnRateAlert("a", "t", windows=((60.0, 10.0),
                                                 (600.0, 2.0)))
    t = 0.0
    fired_at = None
    for _ in range(700):
        _feed(g, 3.0, t)
        a.evaluate(now=t)
        if a.firing and fired_at is None:
            fired_at = t
        t += 1.0
    assert a.firing and fired_at is not None


def test_burn_alert_hysteresis_no_flap_at_boundary():
    timeseries.enable(interval_s=1.0, samples=512, thread=False)
    g = _burn_series()
    a = burnrate.BurnRateAlert("a", "t", windows=((10.0, 10.0),),
                               clear_ratio=0.9, clear_holds=3)
    t = 0.0
    for _ in range(20):
        _feed(g, 20.0, t)
        a.evaluate(now=t)
        t += 1.0
    assert a.firing and a.transitions == 1
    # hover just under the fire threshold but above clear_ratio×factor:
    # a threshold-comparison alert would flap every sample; hysteresis
    # holds it firing with zero transitions
    for _ in range(30):
        _feed(g, 9.5, t)
        a.evaluate(now=t)
        t += 1.0
    assert a.firing and a.transitions == 1
    # drop below clear_ratio×factor: clears only after clear_holds
    # consecutive below evaluations
    for i in range(3):
        _feed(g, 1.0, t)
        a.evaluate(now=t)
        t += 1.0
        # the window average needs time to drain below 9.0 too
    while a.firing and t < 200:
        _feed(g, 1.0, t)
        a.evaluate(now=t)
        t += 1.0
    assert not a.firing and a.transitions == 2


def test_burn_alert_steady_trace_never_flaps():
    timeseries.enable(interval_s=1.0, samples=512, thread=False)
    g = _burn_series()
    a = burnrate.BurnRateAlert("a", "t", windows=((60.0, 10.0),
                                                 (600.0, 2.0)))
    t = 0.0
    for _ in range(300):             # steady nominal burn
        _feed(g, 0.8, t)
        a.evaluate(now=t)
        t += 1.0
    assert not a.firing and a.transitions == 0


def test_burn_alert_unknown_history_freezes_state():
    timeseries.enable(interval_s=1.0, samples=64, thread=False)
    a = burnrate.BurnRateAlert("a", "t")
    st = a.evaluate(now=0.0)         # no samples at all
    assert not st["firing"] and a.transitions == 0


def test_parse_windows_spec_and_defaults():
    assert burnrate.parse_windows("") == burnrate.DEFAULT_WINDOWS
    assert burnrate.parse_windows(None) == burnrate.DEFAULT_WINDOWS
    assert burnrate.parse_windows("120@5,900@1.5") == \
        ((120.0, 5.0), (900.0, 1.5))
    with pytest.raises(ValueError):
        burnrate.parse_windows("120")
    with pytest.raises(ValueError):
        burnrate.parse_windows("a@b")


def test_arm_default_builds_one_alert_per_slo():
    from incubator_mxnet_tpu.telemetry import slo

    timeseries.enable(interval_s=1.0, samples=16, thread=False)
    slo.latency("t_lat", "t_rt_seconds", 0.5)
    slo.latency("t_lat2", "t_rt2_seconds", 0.5)
    try:
        added = burnrate.arm_default()
        names = {f"burn_{s.name}" for s in slo.tracker().slos()}
        assert {a.name for a in burnrate.alerts()} >= names
        assert {a.name for a in added} == names
        # idempotent: a second arm adds nothing
        assert burnrate.arm_default() == []
    finally:
        slo.tracker().remove("t_lat")
        slo.tracker().remove("t_lat2")


# ---------------------------------------------------------------------------
# cost ledger through the REAL serving seams (stub decoder)
# ---------------------------------------------------------------------------

class _StubSlots:
    """Paged-interface stand-in (tests/test_gateway.py recipe)."""

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


def test_ledger_attributes_per_tenant_and_audits_wall():
    capacity.enable()
    capacity.reset()
    gw = _stub_gateway(max_slots=2)
    handles = [gw.submit("m", _prompt(8, seed=i), 6, tenant=tenant)
               for i, tenant in enumerate(["acme", "beta", "acme",
                                           "beta", "crawl"])]
    gw._drive_until(handles, timeout=30)
    led = capacity.ledger_report()
    for tenant in ("acme", "beta", "crawl"):
        row = led["tenants"][tenant]["m"]
        assert row["tokens"] > 0, (tenant, led)
        assert sum(row["device_s"].values()) > 0, (tenant, led)
        assert row["kv_page_s"] > 0, (tenant, led)
        assert "prefill" in row["device_s"], (tenant, led)
        assert "decode" in row["device_s"], (tenant, led)
    # the 5% wall audit (ISSUE 17 acceptance): per-tenant device-
    # seconds sum back to the measured serve wall
    wall = led["measured_wall_s"]
    assert wall > 0
    assert abs(led["device_seconds_sum"] - wall) <= 0.05 * wall, led
    # tokens attributed == tokens generated
    total_tokens = sum(len(h.tokens) for h in handles)
    ledger_tokens = sum(m["tokens"] for t in led["tenants"].values()
                        for m in t.values())
    assert ledger_tokens == total_tokens


def test_queue_wait_tenant_view_and_charge():
    capacity.enable()
    capacity.reset()
    gw = _stub_gateway(max_slots=1)   # force queueing behind 1 slot
    handles = [gw.submit("m", _prompt(8, seed=i), 4, tenant="acme")
               for i in range(4)]
    gw._drive_until(handles, timeout=30)
    rep = registry.report()
    key = 'mx_serve_queue_wait_seconds{tenant="acme"}'
    assert key in rep and rep[key]["count"] == 4, sorted(
        k for k in rep if k.startswith("mx_serve_queue_wait"))
    led = capacity.ledger_report()
    assert led["tenants"]["acme"]["m"]["queue_wait_s"] >= 0


def test_queue_wait_observed_once_despite_preemption():
    capacity.enable()
    capacity.reset()
    gw = _stub_gateway(max_slots=1, tiers="high,low")
    low = gw.submit("m", _prompt(24, seed=1), 12, tenant="bulk",
                    priority="low")
    deadline = time.monotonic() + 10
    while low.state != "dispatched":
        gw.step()
        assert time.monotonic() < deadline
    high = gw.submit("m", _prompt(8, seed=2), 4, tenant="vip",
                     priority="high")
    gw._drive_until([low, high], timeout=30)
    assert low.preemptions >= 1, "victim was never preempted"
    rep = registry.report()
    # the preempted request waited twice but is observed only at its
    # FIRST dispatch — resumes would double-count admission wait
    assert rep['mx_serve_queue_wait_seconds{tenant="bulk"}']["count"] == 1
    assert rep['mx_serve_queue_wait_seconds{tenant="vip"}']["count"] == 1


def test_fleet_report_carries_capacity_rollup():
    from incubator_mxnet_tpu.telemetry import fleet

    capacity.enable()
    capacity.reset()
    capacity.charge_tokens("acme", "m", 5)
    capacity.charge_device_seconds("acme", "m", "decode", 1.25)
    fleet.enable()
    try:
        rep = fleet.fleet_report()
    finally:
        fleet.disable()
    cap = rep["capacity"]
    assert cap["acme"]["m"]["tokens"] == 5
    assert cap["acme"]["m"]["device_s"]["decode"] == pytest.approx(1.25)


def test_charges_are_dead_branch_when_disarmed():
    assert not capacity.is_enabled()
    capacity.charge_tokens("t", "m")
    capacity.charge_device_seconds("t", "m", "decode", 1.0)
    capacity.split_device_seconds(["t"], "m", "prefill", 1.0)
    capacity.charge_kv_page_seconds("t", "m", 1.0)
    capacity.charge_queue_wait("t", "m", 1.0)
    assert capacity.measured_wall_s() == 0.0
    assert "t" not in capacity.ledger_report()["tenants"]
    # disarmed charges never mint series (registry.reset keeps keys
    # from other tests, so look for the tenant only this test used)
    assert not [k for k in registry.report()
                if k.startswith("mx_capacity_") and 'tenant="t"' in k]


# ---------------------------------------------------------------------------
# the disarmed-path <3% gate (satellite 4 / ISSUE acceptance)
# ---------------------------------------------------------------------------

def test_disarmed_observatory_charges_are_dead_branches():
    """Off-path contract: with the observatory disarmed every charge call is
    one module-flag check. A stub scheduler's steps reach the charge seams
    (the prefill one unconditionally, with the step clock's stamps) and
    nothing is banked, counted or registered. (Structural: a CPU run gives
    counts, not speeds.)"""
    assert not capacity.is_enabled()
    registry.reset()
    from incubator_mxnet_tpu.serve.scheduler import Scheduler

    sched = Scheduler(_StubSlots(), max_queue=4)
    req = sched.submit(_prompt(4), 3, tenant="t-off")
    while not req.done:
        sched.step()
    assert sched._cap_last_t is None          # page-second accrual never armed
    wall = capacity.measured_wall_s()
    capacity.split_device_seconds(("t-off",), "serve", "decode", 1.0)
    capacity.charge_tokens("t-off", "serve", 5)
    capacity.charge_kv_page_seconds("t-off", "serve", 2.0)
    capacity.charge_queue_wait("t-off", "serve", 0.5)
    assert capacity.measured_wall_s() == wall
    assert not [k for k in registry.report()
                if k.startswith("mx_capacity_") and 'tenant="t-off"' in k]
    assert "t-off" not in capacity.ledger_report()["tenants"]


# ---------------------------------------------------------------------------
# autoscale advisor: decisions + the seeded diurnal acceptance gate
# ---------------------------------------------------------------------------

def _drive_signals(adv, occ, queue, burn_g, burn, t):
    registry.gauge("mx_serve_slot_occupancy", "occ").set(occ)
    registry.gauge("mx_gateway_queue_depth", "depth",
                   labels={"priority": "normal"}).set(queue)
    burn_g.set(burn)
    timeseries.sample_now(now=t)
    burnrate.evaluate_all(now=t)
    return adv.evaluate(now=t)


def test_advisor_holds_without_history():
    timeseries.enable(interval_s=1.0, samples=64, thread=False)
    adv = AutoscaleAdvisor("m")
    rec = adv.evaluate(now=0.0)
    assert rec["action"] == "hold"
    assert "no history" in rec["reason"]


def test_advisor_scale_up_names_evidence():
    timeseries.enable(interval_s=1.0, samples=256, thread=False)
    burn_g = _burn_series()
    adv = AutoscaleAdvisor("m", fast_window_s=10.0, slow_window_s=30.0)
    t = 0.0
    for _ in range(30):
        rec = _drive_signals(adv, 0.95, 4.0, burn_g, 0.1, t)
        t += 1.0
    assert rec["action"] == "scale_up" and rec["n"] == 1
    assert "mx_serve_slot_occupancy" in rec["reason"]
    assert "mx_gateway_queue_depth" in rec["reason"]
    assert rec["evidence"]["alerts_firing"] == []
    # flash-burst queue depth doubles the ask
    for _ in range(30):
        rec = _drive_signals(adv, 0.99, 40.0, burn_g, 0.1, t)
        t += 1.0
    assert rec["action"] == "scale_up" and rec["n"] == 2


def test_advisor_burn_alert_forces_scale_up():
    timeseries.enable(interval_s=1.0, samples=256, thread=False)
    burn_g = _burn_series()
    burnrate.add("burn_t", "t", windows=((10.0, 5.0),))
    adv = AutoscaleAdvisor("m")
    t = 0.0
    for _ in range(20):              # low occupancy, but budget on fire
        rec = _drive_signals(adv, 0.1, 0.0, burn_g, 50.0, t)
        t += 1.0
    assert rec["action"] == "scale_up"
    assert "burn_t" in rec["reason"]


def test_advisor_scale_down_respects_cooldown():
    timeseries.enable(interval_s=1.0, samples=1024, thread=False)
    burn_g = _burn_series()
    adv = AutoscaleAdvisor("m", fast_window_s=10.0, slow_window_s=30.0,
                           cooldown_s=100.0, log_len=2048)
    t = 0.0
    for _ in range(40):              # surge → scale_up
        _drive_signals(adv, 0.95, 4.0, burn_g, 0.1, t)
        t += 1.0
    # trough right after the surge: within cooldown ⇒ anti-flap hold
    for _ in range(60):
        rec = _drive_signals(adv, 0.05, 0.0, burn_g, 0.1, t)
        t += 1.0
        if t - 40.0 <= 100.0:
            assert rec["action"] != "scale_down", (t, rec)
    # cooldown expired and still idle ⇒ scale_down
    for _ in range(60):
        rec = _drive_signals(adv, 0.05, 0.0, burn_g, 0.1, t)
        t += 1.0
    assert rec["action"] == "scale_down"
    assert "cooldown" not in rec["reason"]


def test_advisor_diurnal_trace_sequence_deterministic():
    """The ISSUE 17 acceptance gate: a seeded `loadgen.diurnal_trace`
    day replayed through a host-side queue model on a VIRTUAL clock
    must produce scale_down in the trough, zero flaps across steady,
    scale_up through the surge/burst — deterministically (no wall
    clock anywhere)."""
    _capwatch, loadgen = _tools()
    events, segments = loadgen.diurnal_trace(
        models={"m": 1.0},
        tenants={"acme": (2.0, "normal"), "beta": (1.0, "normal")},
        seed=7, trough_s=300.0, steady_s=300.0, surge_s=300.0,
        burst_s=120.0, trough_rate=0.2, steady_rate=2.0,
        surge_rate=12.0, burst_rate=60.0)
    assert [s[0] for s in segments] == ["trough", "steady", "surge",
                                       "burst"]

    timeseries.enable(interval_s=5.0, samples=2048, thread=False)
    burn_g = _burn_series()
    adv = AutoscaleAdvisor("m", up_occupancy=0.85, down_occupancy=0.25,
                           fast_window_s=60.0, slow_window_s=300.0,
                           cooldown_s=120.0, burst_queue=16,
                           log_len=4096)
    # host-side queue model: capacity 4 req/s; occupancy = demand/cap
    # clipped, backlog beyond capacity queues; burn follows overload
    cap_rps, dt = 4.0, 5.0
    arrivals = sorted(e.t for e in events)
    i, backlog = 0, 0.0
    t = 0.0
    seg_actions = {name: [] for name, _s, _e in segments}
    end = segments[-1][2]
    while t < end:
        n_arr = 0
        while i < len(arrivals) and arrivals[i] < t + dt:
            n_arr += 1
            i += 1
        served = cap_rps * dt
        demand = backlog + n_arr
        backlog = max(0.0, demand - served)
        occ = min(1.0, demand / served)
        burn = 20.0 if backlog > 30 else (0.5 if occ < 0.9 else 3.0)
        rec = _drive_signals(adv, occ, backlog, burn_g, burn, t)
        for name, s, e in segments:
            if s <= t < e:
                seg_actions[name].append(rec["action"])
        t += dt
    # trough: scale_down recommended, never scale_up
    assert "scale_down" in seg_actions["trough"]
    assert "scale_up" not in seg_actions["trough"]
    # steady: zero flaps — once settled to hold it stays hold
    steady = seg_actions["steady"]
    first_hold = steady.index("hold")
    assert set(steady[first_hold:]) == {"hold"}, steady
    assert "scale_up" not in steady
    # surge and burst: scale_up reached, and never scale_down
    assert "scale_up" in seg_actions["surge"]
    assert "scale_down" not in seg_actions["surge"]
    assert "scale_up" in seg_actions["burst"]
    # collapsed sequence is the canonical diurnal story
    assert adv.recommendations() == ["hold", "scale_down", "hold",
                                     "scale_up"] \
        or adv.recommendations() == ["scale_down", "hold", "scale_up"], \
        adv.recommendations()
    # determinism: the published gauge names the final action
    rep = registry.report()
    assert rep['mx_advisor_recommendation{action="scale_up"}'][
        "value"] == 1


def test_advisor_gateway_arming_via_env(monkeypatch):
    monkeypatch.setenv("MXNET_ADVISOR", "0.0")   # evaluate every step
    gw = _stub_gateway()
    assert set(gw._advisors) == {"m"}
    assert timeseries.is_enabled()
    h = gw.submit("m", _prompt(8), 4, tenant="acme")
    gw._drive_until([h], timeout=30)
    log = gw.advisor_log()
    assert log and all(r["model"] == "m" for r in log)
    assert gw.advisor_log(tail=1)[0] == log[-1]


def test_capwatch_demo_is_reproducible_and_committed():
    import json

    capwatch, _ = _tools()
    rep = capwatch.run_demo()
    assert rep["recommendations"] == ["scale_down", "hold", "scale_up",
                                      "hold"]
    fires = [a for a in rep["alerts"] if a["event"] == "fire"]
    clears = [a for a in rep["alerts"] if a["event"] == "clear"]
    assert len(fires) == 1 and len(clears) == 1
    fixture = os.path.join(REPO, "benchmark", "capwatch_demo.json")
    with open(fixture) as f:
        committed = json.load(f)
    # the virtual clock makes the committed fixture exactly reproducible
    assert committed["recommendations"] == rep["recommendations"]
    assert committed["alerts"] == rep["alerts"]
    assert committed["ledger"]["device_seconds_sum"] == \
        rep["ledger"]["device_seconds_sum"]
    # registry.reset keeps zeroed rows from earlier tests in this
    # process, so compare the fixture's tenant rows as a subset
    for tenant, models in committed["ledger"]["tenants"].items():
        for model, row in models.items():
            assert rep["ledger"]["tenants"][tenant][model] == row, \
                (tenant, model)
