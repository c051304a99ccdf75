"""Pod-scale sharded serving (ISSUE 15): `ServeLayout` partition rules,
`ShardedSlotDecoder` mesh parity, `ReplicaRouter` dispatch, and the
gateway's drain-free weight hot-swap.

Coverage layers, all on the test-wide 8-device forced-CPU mesh:

- host-only layout/rule tests (quick): every decoder param leaf matches
  exactly one partition rule, unmatched leaves raise instead of silently
  replicating, heavy matmuls and the KV pools land on the tp axis;
- router-logic tests against stub replicas (quick): least-loaded page
  scoring, prefix-affinity warm-set restriction, tenant stickiness,
  viability filtering;
- compiled-engine tests: greedy parity with the unsharded engine on a
  1-device mesh (bit-identical) and a tp mesh, the
  two-program-families / zero-steady-state-recompile invariant, a clean
  `shardcheck_report` (SC001/SC004/SC005/SC006) on the real layout, the
  2L-pool-leaves-aliased donation gate from the compile ledger, and the
  gateway hot-swap completing a replayed stream with zero failures.
"""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import np, serve
from incubator_mxnet_tpu.models.gpt import gpt_tiny
from incubator_mxnet_tpu.serve.gateway import Gateway, ModelRegistry
from incubator_mxnet_tpu.serve.router import ReplicaRouter, replica_meshes
from incubator_mxnet_tpu.serve.scheduler import Scheduler
from incubator_mxnet_tpu.serve.sharded import (ServeLayout,
                                               ShardedSlotDecoder,
                                               parse_mesh_spec, serve_mesh)

VOCAB = 97
N_LAYERS = 2        # gpt_tiny


def _prompt(n, seed=0):
    return onp.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(onp.int32)


@pytest.fixture(scope="module")
def net():
    mx.random.seed(11)
    m = gpt_tiny(vocab_size=VOCAB, max_length=64, dropout=0.0)
    m.initialize()
    r = onp.random.RandomState(42)
    for _name, p in m.collect_params().items():
        if p.shape and len(p.shape) >= 2:
            p.set_data(np.array(
                r.normal(0, 0.35, p.shape).astype("float32")))
    return m


def _mesh(tp):
    import jax

    return serve_mesh({"tp": tp}, devices=jax.devices()[:tp])


def _serve_tokens(slots, prompts, max_new=10):
    sched = Scheduler(slots, max_queue=16, seed=0)
    reqs = [sched.submit(p, max_new, temperature=1.0) for p in prompts]
    for _ in range(4000):
        sched.step()
        if all(r.done for r in reqs):
            break
    assert all(r.done for r in reqs)
    return [r.result() for r in reqs]


# ---------------------------------------------------------------------------
# layout rules — host-only (quick)
# ---------------------------------------------------------------------------

def test_every_param_leaf_matches_exactly_one_rule(net):
    import jax

    from incubator_mxnet_tpu.models.decoding import GPTDecoder
    from incubator_mxnet_tpu.serve.sharded import _path_str

    layout = ServeLayout(_mesh(1))
    params = GPTDecoder(net)._params
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    assert flat
    for path, _leaf in flat:
        p = _path_str(path)
        hits = [rx.pattern for rx, _ in layout._compiled if rx.search(p)]
        assert len(hits) == 1, (p, hits)
        layout.spec_for(p)      # resolves without error


def test_unmatched_leaf_raises_no_replicated_fallback():
    layout = ServeLayout(_mesh(1))
    with pytest.raises(ValueError, match="no partition rule"):
        layout.spec_for("layers/0/mystery_w")
    # the rules are anchored: a path without a layer's index names no leaf
    with pytest.raises(ValueError, match="no partition rule"):
        layout.spec_for("layers/qkv_w")
    with pytest.raises(ValueError, match="no partition rule"):
        layout.spec_for("head_w")


def test_heavy_leaves_and_pools_land_on_tp():
    layout = ServeLayout(_mesh(2))
    # matrices are stored (in, out): row-parallel = tp on the FIRST axis,
    # column-parallel ffn1 = tp on the last, its bias along with it
    for name in ("layers/0/qkv_w", "layers/11/proj_w", "layers/47/ffn2_w"):
        assert tuple(layout.spec_for(name))[0] == "tp", name
    assert tuple(layout.spec_for("layers/3/ffn1_w"))[-1] == "tp"
    assert tuple(layout.spec_for("layers/3/ffn1_b")) == ("tp",)
    # norms / embeddings / an untied head are replicated EXPLICITLY (not a
    # fallback)
    for name in ("layers/0/ln1_g", "layers/0/qkv_b", "embed", "pos",
                 "lnf_g", "head"):
        assert "tp" not in tuple(layout.spec_for(name)), name
    # pools shard the head axis; scale planes follow
    # one spec for every leaf of the pools pytree, pages and scale planes
    assert tuple(layout.pool_spec()) == (None, "tp")


def test_parse_mesh_spec_grammar():
    assert parse_mesh_spec(4) == {"tp": 4}
    assert parse_mesh_spec("4") == {"tp": 4}
    assert parse_mesh_spec("tp=2") == {"tp": 2}
    assert parse_mesh_spec("fsdp=2,tp=4") == {"fsdp": 2, "tp": 4}
    assert parse_mesh_spec("") == {"tp": 1}
    with pytest.raises(ValueError, match="mesh spec"):
        parse_mesh_spec("tp:4")


def test_replica_meshes_disjoint_slices():
    import jax

    meshes = replica_meshes("tp=2", 2, devices=jax.devices())
    assert len(meshes) == 2
    seen = [d for m in meshes for d in m.devices.flat]
    assert len(seen) == len(set(seen)) == 4
    with pytest.raises(ValueError, match="need"):
        replica_meshes("tp=4", 3, devices=jax.devices())


def test_divisibility_check_is_loud(net):
    with pytest.raises(ValueError, match="divisible"):
        ShardedSlotDecoder(net, mesh=serve_mesh({"tp": 3}),
                           max_slots=2, max_len=64, n_pages=16)


# ---------------------------------------------------------------------------
# router logic — stub replicas (quick)
# ---------------------------------------------------------------------------

class _StubCache:
    def __init__(self, warm):
        self._warm = warm

    def shared_tokens(self, prompt):
        return self._warm


class _StubRep:
    class _Alloc:
        def __init__(self, free, usable):
            self.free_pages = free
            self.usable_pages = usable

    class _Sched:
        def __init__(self, depth):
            self.queue_depth = depth

    class _Slots:
        pass

    def __init__(self, free=8, usable=8, depth=0, warm=None, label="r"):
        self.slots = self._Slots()
        self.slots.allocator = self._Alloc(free, usable)
        if warm is not None:
            self.slots.prefix_cache = _StubCache(warm)
        self.sched = self._Sched(depth)
        self.label = label


def test_router_least_loaded_picks_free_pages():
    r = ReplicaRouter(affinity="off")
    a = _StubRep(free=2, usable=8, label="a")
    b = _StubRep(free=7, usable=8, label="b")
    assert r.pick([a, b]) is b
    # a deep queue penalizes an otherwise-free replica
    c = _StubRep(free=8, usable=8, depth=8, label="c")
    assert r.pick([b, c]) is b
    # viability filter wins over score
    assert r.pick([a, b], viable=lambda rep: rep is a) is a
    assert r.pick([], viable=None) is None
    assert r.pick([a, b], viable=lambda rep: False) is None


def test_router_prefers_warm_prefix_replica():
    r = ReplicaRouter(affinity="prefix")
    cold = _StubRep(free=8, usable=8, warm=0, label="cold")
    warm = _StubRep(free=2, usable=8, warm=32, label="warm")
    # warm pages beat free pages
    assert r.pick([cold, warm], prompt=_prompt(40)) is warm
    # nothing warm anywhere -> pure least-loaded
    cold2 = _StubRep(free=5, usable=8, warm=0, label="cold2")
    assert r.pick([cold, cold2], prompt=_prompt(40)) is cold
    # a warm replica that fails viability is skipped, not waited on
    assert r.pick([cold, warm], prompt=_prompt(40),
                  viable=lambda rep: rep is cold) is cold


def test_router_tenant_affinity_stable_and_validated():
    r = ReplicaRouter(affinity="tenant")
    reps = [_StubRep(label=f"r{i}") for i in range(4)]
    picks = {r.pick(reps, tenant="alice").label for _ in range(5)}
    assert len(picks) == 1                      # stable across calls
    # preferred replica not viable -> least-loaded among the viable
    pref = r.pick(reps, tenant="alice")
    other = r.pick(reps, tenant="alice",
                   viable=lambda rep: rep is not pref)
    assert other is not pref
    with pytest.raises(ValueError, match="affinity"):
        ReplicaRouter(affinity="bogus")


# ---------------------------------------------------------------------------
# compiled engines — parity, program families, shardcheck, donation
# ---------------------------------------------------------------------------

def test_one_device_mesh_greedy_parity(net):
    prompts = [_prompt(7, seed=1), _prompt(11, seed=2)]
    base = serve.SlotDecoder(net, max_slots=2, max_len=64, n_pages=24)
    try:
        want = _serve_tokens(base, prompts)
    finally:
        base.release()
    sh = ShardedSlotDecoder(net, mesh=_mesh(1), max_slots=2, max_len=64,
                            n_pages=24)
    try:
        got = _serve_tokens(sh, prompts)
    finally:
        sh.release()
    assert got == want      # bit-identical greedy stream


# SC005 takes an all-gather for a sharded leaf gathered whole when their BYTES
# agree. A layer's leaves are arrays of their own since PR 32, and at
# `gpt_tiny`'s widths they are as small as the activations the tp pair
# gathers before ffn1: 2 slots x 64 features is `ffn1_b`'s 512 bytes, a
# 64-token chunk x 64 features `proj_w`'s 16 KiB. Three slots and a 32-token
# chunk agree with no leaf; the engines compared take the same settings.
_CLEAN = dict(max_slots=3, max_len=64, n_pages=24, prefill_chunk=32)


def test_tp_mesh_parity_two_families_and_clean_shardcheck(net):
    prompts = [_prompt(7, seed=1), _prompt(11, seed=2)]
    base = serve.SlotDecoder(net, **_CLEAN)
    try:
        want = _serve_tokens(base, prompts)
    finally:
        base.release()

    from incubator_mxnet_tpu.telemetry import compiles

    compiles.enable()
    try:
        compiles.reset()
        sh = ShardedSlotDecoder(net, mesh=_mesh(2), **_CLEAN)
        try:
            got = _serve_tokens(sh, prompts)
            assert got == want
            programs = sh.xla_program_count()
            # steady state: 3x more traffic, zero new programs
            _serve_tokens(sh, [_prompt(9, seed=s) for s in range(6)])
            assert sh.xla_program_count() == programs
            report = sh.shardcheck_report()
            for fam in ("prefill", "decode"):
                assert report[fam].findings == [], (
                    fam, [(f.rule, f.message) for f in report[fam].findings])
            # the TP pair's per-token collective is the all-reduce;
            # nothing re-materializes a sharded operand on the hot path
            assert "all-reduce" in report["decode"].collectives
            # XLA's own donation map: all 2L per-layer pool leaves alias
            mem = compiles.ledger("serve.decode")[-1]["memory"]
            aliased = mem.get("aliased_params")
            assert aliased is not None
            assert len(aliased) >= 2 * N_LAYERS, aliased
        finally:
            sh.release()
    finally:
        compiles.disable()
        compiles.reset()


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_tp_mesh_step_in_flight_one_decode_program(net, kv_dtype):
    """The sharded engine inherits `decode_step`: the tokens of a launch
    come back replicated over the mesh and the next launch takes them as
    they are, so the first launch (zeros placed the same way) and every
    later one share ONE compiled decode program; tokens are those of the
    same engine fetching every step before it launches the next."""
    prompts = [_prompt(n, seed=i) for i, n in enumerate((5, 19, 33, 8, 12))]
    outs = []
    for serial in (False, True):
        slots = ShardedSlotDecoder(net, mesh=_mesh(2), max_slots=3,
                                   max_len=64, prefill_chunk=16,
                                   page_tokens=8, kv_dtype=kv_dtype)
        if serial:
            inner = slots.decode_step
            slots.decode_step = lambda *a: onp.asarray(inner(*a))
        outs.append(_serve_tokens(slots, prompts, max_new=7))
        assert slots._decode_jit._cache_size() == 1
        assert tuple(slots._tokens.sharding.spec) == ()
    assert outs[0] == outs[1] and all(len(o) == 7 for o in outs[0])


def test_tp_mesh_int8_kv_runs_with_clean_shardcheck(net):
    sh = ShardedSlotDecoder(net, mesh=_mesh(2), kv_dtype="int8", **_CLEAN)
    try:
        toks = _serve_tokens(sh, [_prompt(7, seed=1)])
        assert toks[0] and len(toks[0]) <= 10
        report = sh.shardcheck_report()
        for fam in ("prefill", "decode"):
            assert report[fam].findings == [], (
                fam, [(f.rule, f.message) for f in report[fam].findings])
    finally:
        sh.release()


def test_hbm_budget_gate_fires_sc006(net):
    sh = ShardedSlotDecoder(net, mesh=_mesh(2), max_slots=2, max_len=64,
                            n_pages=24, hbm_budget_gb=1e-6)
    try:
        report = sh.shardcheck_report()
        rules = {f.rule for f in report["decode"].findings}
        assert "SC006" in rules
    finally:
        sh.release()


# ---------------------------------------------------------------------------
# gateway: replica routing end-to-end + drain-free hot swap
# ---------------------------------------------------------------------------

def test_gateway_replicas_route_and_hot_swap_drain_free(net):
    reg = ModelRegistry(total_pages=96)
    reg.add("m", net, replicas=2, mesh="tp=2", max_slots=2, max_len=64)
    gw = Gateway(reg, seed=0)
    try:
        # phase 1: spread traffic across both replicas
        first = [gw.submit("m", _prompt(6, seed=s), 8) for s in range(6)]
        for _ in range(4000):
            gw.step()
            if all(r.done for r in first):
                break
        assert all(r.done for r in first)
        assert {r.replica for r in first} == {"m#0", "m#1"}

        # phase 2: swap weights mid-stream — one replica at a time,
        # zero failed requests, no drain
        inflight = [gw.submit("m", _prompt(6, seed=10 + s), 8)
                    for s in range(4)]
        gw.step()
        r = onp.random.RandomState(5)
        for _name, p in net.collect_params().items():
            if p.shape and len(p.shape) >= 2:
                p.set_data(np.array(
                    r.normal(0, 0.3, p.shape).astype("float32")))
        swapped = gw.hot_swap("m")
        assert swapped == {"m#0": True, "m#1": True}
        for _ in range(4000):
            gw.step()
            if all(q.done for q in inflight):
                break
        assert all(q.done for q in inflight)
        assert all(q.result() for q in inflight)    # no failures

        # a second swap with unchanged weights is a no-op per replica
        assert gw.hot_swap("m") == {"m#0": False, "m#1": False}
    finally:
        gw.shutdown()


def test_gateway_single_replica_backcompat(net):
    reg = ModelRegistry(total_pages=48)
    reg.add("s", net, max_slots=2, max_len=64)
    gw = Gateway(reg, seed=0)
    try:
        req = gw.submit("s", _prompt(6, seed=1), 6)
        for _ in range(2000):
            gw.step()
            if req.done:
                break
        assert req.done
        # single-replica label is the model name, and the pre-replica
        # metric series stay unlabeled (no {replica=} view emitted)
        assert gw._models["s"].replicas[0].label == "s"
        counts = gw.xla_program_counts()
        assert isinstance(counts["s"], int)
    finally:
        gw.shutdown()
