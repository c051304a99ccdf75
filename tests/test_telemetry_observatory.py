"""Compile & HBM observatory (ISSUE 10): recompile forensics with one
fixture per root cause, program-family ledger completeness over the real
entry points, HBM census attribution + the SC006 crosscheck, the OOM
post-mortem seam, and the off-path overhead gate."""
import glob
import json
import os
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import np
from incubator_mxnet_tpu.fault import injection
from incubator_mxnet_tpu.telemetry import compiles, hbm, registry, tracing

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _clean_observatory():
    yield
    compiles.disable()
    compiles.reset()
    hbm.disable()
    hbm.disarm_memwatch()
    hbm.reset()
    injection.clear_injection()
    registry.reset()
    tracing.disable()
    tracing.reset()


@pytest.fixture
def armed():
    compiles.enable()
    hbm.enable()
    return None


# ---------------------------------------------------------------------------
# recompile forensics: one fixture per cause, each naming the offender
# ---------------------------------------------------------------------------

def test_recompile_cause_shape(armed):
    f = compiles.ledgered_jit(lambda x: x * 2, family="t.shape")
    f(jnp.ones((4,), "float32"))
    f(jnp.ones((8,), "float32"))
    e1, e2 = compiles.ledger("t.shape")
    assert e1["cause"] == "first"
    assert e2["cause"] == "shape"
    assert "arg 0" in e2["detail"]
    assert "(4,)" in e2["detail"] and "(8,)" in e2["detail"]
    # the recompile surfaced on the labeled counter
    c = registry.counter("mx_jit_recompiles_total",
                         labels={"program": "t.shape", "cause": "shape"})
    assert c.value == 1


def test_recompile_cause_dtype(armed):
    f = compiles.ledgered_jit(lambda x: x + 1, family="t.dtype")
    f(jnp.ones((4,), "float32"))
    f(jnp.ones((4,), "int32"))
    e2 = compiles.ledger("t.dtype")[-1]
    assert e2["cause"] == "dtype"
    assert "arg 0" in e2["detail"]
    assert "float32" in e2["detail"] and "int32" in e2["detail"]


def test_recompile_cause_weak_type(armed):
    f = compiles.ledgered_jit(lambda x: x * 3, family="t.weak")
    f(jnp.ones((), "float32"))          # weak_type=False
    f(jnp.asarray(2.0))                 # weak_type=True, same shape/dtype
    e2 = compiles.ledger("t.weak")[-1]
    assert e2["cause"] == "weak_type", e2
    assert "arg 0" in e2["detail"]


def test_recompile_cause_static_arg(armed):
    f = compiles.ledgered_jit(lambda x, n: x * n, family="t.static",
                              static_argnums=(1,))
    x = jnp.ones((4,), "float32")
    f(x, 3)
    f(x, 4)
    e2 = compiles.ledger("t.static")[-1]
    assert e2["cause"] == "static_arg"
    assert "arg 1" in e2["detail"]
    assert "3" in e2["detail"] and "4" in e2["detail"]


def test_recompile_cause_new_bucket(armed):
    f = compiles.ledgered_jit(
        lambda x: x.sum(), family="t.bucket",
        bucket=lambda args, kwargs: int(args[0].shape[0]))
    f(jnp.ones((4,), "float32"))
    f(jnp.ones((8,), "float32"))        # shape changed, but a NEW bucket
    f(jnp.ones((4,), "float32"))        # cache hit: no entry
    entries = compiles.ledger("t.bucket")
    assert [e["cause"] for e in entries] == ["first", "new_bucket"]
    assert entries[-1]["bucket"] == 8
    rep = compiles.ledger_report()["t.bucket"]
    assert rep["buckets"] == [4, 8]
    assert rep["causes"] == {"new_bucket": 1}


def test_forensics_arity_and_nested_containers(armed):
    # arity change is a static_arg diff, not a crash
    cause, detail = compiles.diagnose(
        compiles.signature_of((jnp.ones((2,)),)),
        compiles.signature_of((jnp.ones((2,)), jnp.ones((2,)))))
    assert cause == "static_arg" and "arity" in detail
    # an aval change nested inside a params tuple still names the leaf
    cause, detail = compiles.diagnose(
        compiles.signature_of(((jnp.ones((2, 2)), jnp.ones((3,))),)),
        compiles.signature_of(((jnp.ones((2, 2)), jnp.ones((5,))),)))
    assert cause == "shape" and "arg 0[1]" in detail


# ---------------------------------------------------------------------------
# ledger completeness: every real program family reports in
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gpt():
    from incubator_mxnet_tpu.models.gpt import gpt_tiny

    mx.random.seed(7)
    net = gpt_tiny(vocab_size=64, max_length=64, dropout=0.0)
    net.initialize()
    return net


def _drive_engine(net, n_req=2):
    from incubator_mxnet_tpu import serve

    eng = serve.ServeEngine(net, max_slots=2, max_len=64, max_queue=8)
    r = onp.random.RandomState(0)
    reqs = [eng.submit(r.randint(0, 64, (5 + i,)).astype(onp.int32), 4)
            for i in range(n_req)]
    while not all(q.done for q in reqs):
        eng.step()
    return eng


def test_ledger_covers_every_program_family(armed, tiny_gpt):
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import DataParallel

    eng = _drive_engine(tiny_gpt)

    net = gluon.nn.Dense(1, in_units=4)
    net.initialize()
    dp = DataParallel(net, gluon.loss.L2Loss(), mx.optimizer.SGD(0.1))
    X = onp.zeros((8, 4), "float32")
    dp.step(np.array(X), np.array(X[:, :1]))

    # eager cacheable op. The eager jit cache keys on (op fn, static args)
    # — NOT shapes — and is process-global, so any earlier suite module
    # that touched `add` leaves the program warm and no compile event can
    # fire here; evict its entries so this call is a fresh compile.
    from incubator_mxnet_tpu.ndarray import ndarray as nd
    for k in [k for k in nd._JIT_CACHE
              if getattr(k[0], "__name__", "") == "add"]:
        nd._JIT_CACHE.pop(k)
    np.add(np.array([1.0]), np.array([2.0]))

    h = gluon.nn.Dense(2, in_units=3)
    h.initialize()
    h.hybridize()
    x = np.array(onp.ones((1, 3), "float32"))
    h(x)                                          # eager deferred-init pass
    h(x)                                          # cached-graph warmup

    rep = compiles.ledger_report()
    for fam in ("serve.prefill", "serve.decode", "train.DataParallel.step",
                "eager.add", "cached_op:Dense"):
        assert fam in rep, (fam, sorted(rep))
        assert rep[fam]["compiles"] >= 1
        assert rep[fam]["last_fingerprint"], fam
    # cost/memory stats came from XLA's own accounting
    for fam in ("serve.prefill", "serve.decode", "train.DataParallel.step"):
        assert rep[fam]["flops"] and rep[fam]["flops"] > 0, fam
        assert rep[fam]["peak_bytes"] and rep[fam]["peak_bytes"] > 0, fam
    # the serving invariant, now with attribution: exactly first compiles,
    # no steady-state recompile causes on the serve families
    assert not rep["serve.decode"]["causes"]
    # the engine's donation map is on the ledger (KV aliasing contract)
    decode = compiles.ledger("serve.decode")[-1]
    assert decode["donate"], decode["donate"]
    assert eng.xla_program_count() >= 2           # wrapper passthrough


def test_gateway_models_are_attributed_per_model(armed, tiny_gpt):
    from incubator_mxnet_tpu.serve import Gateway, ModelRegistry

    reg = ModelRegistry()
    reg.add("gpta", tiny_gpt, max_slots=2, max_len=64)
    gw = Gateway(reg)
    r = onp.random.RandomState(1)
    gw.generate("gpta", r.randint(0, 64, (6,)).astype(onp.int32), 3)
    rep = compiles.ledger_report()
    assert "serve:gpta.prefill" in rep and "serve:gpta.decode" in rep
    c = hbm.census(top_k=0)
    assert c["owners"].get("serve:gpta.params", 0) > 0
    assert c["owners"].get("serve:gpta.kv_pool", 0) > 0


# ---------------------------------------------------------------------------
# HBM census + SC006 crosscheck
# ---------------------------------------------------------------------------

def test_census_attribution_first_claim_and_weak_binding(armed):
    a = jnp.ones((256,), "float32")               # 1 KiB
    b = jnp.ones((512,), "float32")               # 2 KiB
    alive = {"on": True}

    def probe():
        return {"arrays": [a, b], "detail": {"n": 2},
                "derived": {"half": a.nbytes}} if alive["on"] else None

    hbm.register_owner("t_owner", probe)
    hbm.register_owner("t_dup", lambda: [a])      # second claim loses
    c = hbm.census()
    assert c["owners"]["t_owner"] == a.nbytes + b.nbytes
    assert c["owners"]["t_dup"] == 0
    assert c["derived"]["t_owner.half"] == a.nbytes
    assert c["detail"]["t_owner"] == {"n": 2}
    assert c["total"] >= c["owners"]["t_owner"]
    assert c["unattributed"] == c["total"] - a.nbytes - b.nbytes
    # wide K so other tests' module-scope params can't crowd ours out
    assert any(t["owner"] == "t_owner"
               for t in hbm.census(top_k=4096)["top"])
    # weakly-bound: a dead source drops out instead of erroring
    alive["on"] = False
    assert "t_owner" not in hbm.census()["owners"]
    # armed collector exposes the gauges through the registry report
    text = registry.exposition()
    assert "mx_hbm_live_bytes_total" in text
    assert 'mx_hbm_live_bytes{owner="t_dup"}' in text


def test_watchdog_warns_once_per_streak(armed):
    hoard = []
    warned = []
    for i in range(4):
        hoard.append(jnp.ones((1024 * (i + 1),), "float32"))
        warned.append(hbm.watchdog_observe(window=3, min_growth=1))
    assert warned[2] is True or warned[3] is True
    # one warning per streak: once warned, continued growth stays quiet
    hoard.append(jnp.ones((1 << 16,), "float32"))
    assert hbm.watchdog_observe(window=3, min_growth=1) is False
    assert registry.counter("mx_hbm_watchdog_warnings_total").value == 1


def test_sc006_crosscheck_within_15_percent(armed, tiny_gpt):
    eng = _drive_engine(tiny_gpt)
    xc = eng._sched.slots.hbm_crosscheck()
    assert xc["sc006_bytes"] > 0 and xc["census_bytes"] > 0
    assert 0.85 <= xc["ratio"] <= 1.15, xc
    assert set(xc["owners"]) == {"serve.kv_pool", "serve.params"}


# ---------------------------------------------------------------------------
# OOM post-mortem at the serve_step seam (injected RESOURCE_EXHAUSTED)
# ---------------------------------------------------------------------------

def test_oom_postmortem_dumps_census_and_ledger(armed, tiny_gpt,
                                                tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    eng = _drive_engine(tiny_gpt)                 # populate ledger + owners
    injection.configure_injection({"serve_step": (1.0, 0, 1, "oom")})
    r = onp.random.RandomState(3)
    eng.submit(r.randint(0, 64, (6,)).astype(onp.int32), 3)
    with pytest.raises(injection.InjectedResourceExhausted) as ei:
        eng.step()
    assert hbm.is_resource_exhausted(ei.value)

    dumps = glob.glob(str(tmp_path / "flightrec_oom_serve_step_*.json"))
    assert len(dumps) == 1, dumps
    with open(dumps[0]) as f:
        payload = json.load(f)
    assert payload["error"]["type"] == "InjectedResourceExhausted"
    assert "RESOURCE_EXHAUSTED" in payload["error"]["message"]
    census = payload["context"]["hbm_census"]
    assert census["owners"]["serve.kv_pool"] > 0
    assert census["owners"]["serve.params"] > 0
    assert census["top"], "top-K buffers missing from the post-mortem"
    ledger = payload["context"]["compile_ledger"]
    assert "serve.decode" in ledger["report"]
    assert "serve.prefill" in ledger["report"]
    assert ledger["tail"]["serve.decode"][-1]["cause"] == "first"
    assert registry.counter("mx_oom_postmortems_total",
                            labels={"where": "serve_step"}).value == 1

    # the memwatch CLI renders the dump end to end
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import memwatch
    finally:
        sys.path.pop(0)
    assert memwatch.main(["--postmortem", dumps[0]]) == 0


def test_non_oom_faults_skip_the_postmortem(armed, tiny_gpt, tmp_path,
                                            monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    eng = _drive_engine(tiny_gpt)
    injection.configure_injection({"serve_step": (1.0, 0, 1)})  # plain fault
    r = onp.random.RandomState(4)
    eng.submit(r.randint(0, 64, (6,)).astype(onp.int32), 3)
    with pytest.raises(injection.FaultInjected):
        eng.step()
    assert not glob.glob(str(tmp_path / "flightrec_oom_*.json"))
    assert not hbm.is_resource_exhausted(ValueError("boring"))


def test_postmortem_env_overrides(monkeypatch):
    exc = injection.InjectedResourceExhausted("t", 1)
    # disabled + unset: follows arming (off)
    monkeypatch.delenv("MXNET_OOM_POSTMORTEM", raising=False)
    assert hbm.maybe_oom_postmortem("t", exc) is None
    # MXNET_OOM_POSTMORTEM=0 forces off even when telemetry is armed
    hbm.enable()
    monkeypatch.setenv("MXNET_OOM_POSTMORTEM", "0")
    assert hbm.maybe_oom_postmortem("t", exc) is None


# ---------------------------------------------------------------------------
# off-path contract: MXNET_TELEMETRY unset leaves the hot path alone
# ---------------------------------------------------------------------------

def test_off_path_ledger_is_dead_and_reads_no_clock(count_clock_reads):
    """Disarmed, the ledgered wrapper is one flag check and the call itself:
    it reads no clock, never asks the jitted object for its cache size, and
    leaves no ledger entry. (Structural: a CPU run gives counts, not
    speeds — the wall-clock gate this replaces failed on a loaded host.)"""
    assert not compiles.is_enabled() and not hbm.is_enabled()

    class Jitted:
        calls = cache_size_calls = 0

        def __call__(self, a, scale=1.0):
            Jitted.calls += 1
            return a * scale

        def _cache_size(self):
            Jitted.cache_size_calls += 1
            return 1

    clock = count_clock_reads(compiles)
    w = compiles.instrument_jit(Jitted(), "t.off")
    for i in range(300):
        assert w(float(i), scale=2.0) == 2.0 * i     # arguments pass through
    assert Jitted.calls == 300 and Jitted.cache_size_calls == 0
    assert clock.reads == 0 and compiles.ledger() == {}
    # armed, the same wrapper does look (so the counts above are the off path)
    compiles.enable()
    w(1.0)
    assert Jitted.cache_size_calls == 2 and clock.reads


def test_knobs_are_documented():
    from incubator_mxnet_tpu import util

    knobs = util.env_knobs()
    assert "MXNET_MEMWATCH_INTERVAL" in knobs
    assert "MXNET_OOM_POSTMORTEM" in knobs


def test_env_knobs_arm_observatory_at_import():
    import subprocess
    import sys

    code = ("import incubator_mxnet_tpu as mx; "
            "from incubator_mxnet_tpu.telemetry import compiles, hbm; "
            "from incubator_mxnet_tpu.ndarray import ndarray as nd; "
            "print(compiles.is_enabled(), hbm.is_enabled(), "
            "nd._COMPILE_HOOK is not None, nd._OOM_HOOK is not None)")
    env = dict(os.environ, MXNET_TELEMETRY="1", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "True True True True" in out.stdout, out.stdout


def test_roofline_unknown_device_warns_once(caplog):
    import logging

    from incubator_mxnet_tpu.telemetry import roofline

    roofline._WARNED_DEVICES.discard("v99test")
    with caplog.at_level(
            logging.WARNING,
            logger="incubator_mxnet_tpu.telemetry.roofline"):
        r = roofline.analyze([], device="v99test")
        roofline.analyze([], device="v99test")     # second lookup: quiet
    assert r["meta"]["peak_gbs"] is None
    warns = [rec for rec in caplog.records
             if "PEAK_HBM_GBS" in rec.getMessage()]
    assert len(warns) == 1
    msg = warns[0].getMessage()
    assert "v99test" in msg and "v5e" in msg and "peak_gbs=" in msg
