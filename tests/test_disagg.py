"""serve.disagg — disaggregated prefill/decode serving (ISSUE 19).

Stub-engine logic tests (pure host arithmetic over REAL
PageAllocator/PrefixCache — the test_gateway.py recipe, so a request
prefilled on replica A and adopted on replica B must continue the same
arithmetic token run) cover: role threading through
``ModelRegistry.add(prefill_replicas=, decode_replicas=)``, the
migration pump's refcount handoff and byte audit, the
``page_migration`` chaos seam's co-located fallback with ZERO page
leak, the decode-side page-exhausted fallback, role-aware elastic
crash replacement, and the preserved gateway invariants (priority
preemption, dispatch scoping). The real-engine test is the acceptance
gate: a request prefilled on replica A and decoded on replica B
produces BIT-IDENTICAL greedy tokens to a single-replica
``role="both"`` pod, with the decode replica's compile ledger showing
zero prefill families.
"""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import np, serve
from incubator_mxnet_tpu.fault import injection
from incubator_mxnet_tpu.models.gpt import gpt_tiny
from incubator_mxnet_tpu.serve import disagg
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
    decode increments — so the tokens of a request that migrated
    mid-flight must be the same arithmetic run ``[plen, plen+1, ...]``
    as one served co-located. ``page_bytes`` makes the migration byte
    audit exact."""

    def __init__(self, max_slots=2, max_len=64, page_tokens=16,
                 prefill_chunk=64, n_pages=None, page_bytes=2048):
        self.max_slots = max_slots
        self.max_len = max_len
        self.page_tokens = page_tokens
        self.prefill_chunk = prefill_chunk
        self.page_bytes = page_bytes
        pages_per_slot = -(-max_len // page_tokens)
        self.allocator = PageAllocator(
            n_pages if n_pages is not None
            else max_slots * pages_per_slot + 1, page_tokens)
        self.prefix_cache = PrefixCache(self.allocator)
        self.released = False

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
        self.released = True


def _prompt(n, seed=0):
    return onp.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(onp.int32)


def _disagg_gateway(n_prefill=1, n_decode=1, decode_pages=None,
                    prefill_pages=None, **gw_kwargs):
    """1-model disaggregated gateway over prebuilt stubs: the first
    `n_prefill` stubs take the prefill role."""
    stubs = ([_StubSlots(n_pages=prefill_pages)
              for _ in range(n_prefill)]
             + [_StubSlots(n_pages=decode_pages)
                for _ in range(n_decode)])
    reg = serve.ModelRegistry()
    reg.add("m", stubs, prefill_replicas=n_prefill,
            decode_replicas=n_decode)
    return serve.Gateway(reg, **gw_kwargs), stubs


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


def _free_pages(stub):
    return stub.allocator.free_pages


# ---------------------------------------------------------------------------
# registry role threading (quick)
# ---------------------------------------------------------------------------

def test_registry_disagg_validation():
    reg = serve.ModelRegistry()
    with pytest.raises(ValueError):                 # pair, not half
        reg.add("a", _StubSlots(), prefill_replicas=1)
    with pytest.raises(ValueError):
        reg.add("b", _StubSlots(), decode_replicas=1)
    with pytest.raises(ValueError):                 # mutually exclusive
        reg.add("c", [_StubSlots(), _StubSlots()], replicas=2,
                prefill_replicas=1, decode_replicas=1)
    with pytest.raises(ValueError):                 # >= 1 of each role
        reg.add("d", [_StubSlots()], prefill_replicas=1,
                decode_replicas=0)
    # prebuilt count must equal the role sum
    reg2 = serve.ModelRegistry()
    reg2.add("m", [_StubSlots(), _StubSlots(), _StubSlots()],
             prefill_replicas=1, decode_replicas=1)
    with pytest.raises(ValueError) as ei:
        serve.Gateway(reg2)
    assert "pre-built" in str(ei.value)
    # a single prebuilt engine cannot be disaggregated
    reg3 = serve.ModelRegistry()
    reg3.add("m", _StubSlots(), prefill_replicas=1, decode_replicas=1)
    with pytest.raises(ValueError):
        serve.Gateway(reg3)


def test_registry_disagg_page_split():
    reg = serve.ModelRegistry(total_pages=100)
    reg.add("m", object(), prefill_replicas=1, decode_replicas=2)
    per_p, per_d = reg.rebalance_pages_disagg("m", 1, 2)
    # the prefill sliver: ~25% of the cut; decode gets the rest
    assert per_p == 25 and per_d == 37
    assert per_p + 2 * per_d <= 100
    with pytest.raises(PagePoolExhausted):
        reg.rebalance_pages_disagg("m", 1, 100)
    with pytest.raises(ValueError):
        reg.rebalance_pages_disagg("nope", 1, 1)
    # no joint budget: engines size their own pools
    assert serve.ModelRegistry().add(
        "m", object(), prefill_replicas=1,
        decode_replicas=1).rebalance_pages_disagg("m", 1, 1) == (None,
                                                                None)


def test_roles_assigned_and_dispatch_scoped():
    gw, _stubs = _disagg_gateway(n_prefill=1, n_decode=2)
    try:
        m = gw._models["m"]
        assert m.disagg
        assert [r.role for r in m.replicas] == ["prefill", "decode",
                                                "decode"]
        assert [r.label for r in m.replicas] == ["m#0", "m#1", "m#2"]
        # dispatch (and preemption-victim search) never targets a
        # decode replica
        assert [r.role for r in gw._dispatch_reps(m)] == ["prefill"]
        # a homogeneous model is untouched by the scoping
        reg = serve.ModelRegistry()
        reg.add("h", _StubSlots())
        gw2 = serve.Gateway(reg)
        try:
            hm = gw2._models["h"]
            assert not hm.disagg
            assert gw2._dispatch_reps(hm) is hm.replicas
        finally:
            gw2.shutdown(drain=False)
    finally:
        gw.shutdown(drain=False)


def test_mxnet_disagg_env_knob_defaults_roles():
    from incubator_mxnet_tpu.test_utils import environment

    with environment({"MXNET_DISAGG": "1",
                      "MXNET_SERVE_PREFILL_REPLICAS": "1",
                      "MXNET_SERVE_DECODE_REPLICAS": "2"}):
        net = gpt_tiny(vocab_size=VOCAB, max_length=64, dropout=0.0)
        net.initialize()
        reg = serve.ModelRegistry()
        reg.add("m", net, max_slots=2, max_len=64)
        gw = serve.Gateway(reg)
        try:
            roles = [r.role for r in gw._models["m"].replicas]
            assert roles == ["prefill", "decode", "decode"]
        finally:
            gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# the migration plane (stub engines, quick)
# ---------------------------------------------------------------------------

def test_migrated_request_continues_token_run_and_audits_bytes():
    gw, stubs = _disagg_gateway()
    try:
        pages0 = _counter('mx_serve_page_migration_pages_total'
                          '{model="m"}')
        bytes0 = _counter('mx_serve_page_migration_bytes_total'
                          '{model="m"}')
        h = gw.submit("m", _prompt(20), 6)
        _drive(gw, [h])
        # the stub run is arithmetic: first token = prompt length, then
        # +1 per decode step — ONE unbroken run across the migration
        assert h.state == "done"
        assert h.tokens == list(range(20, 26))
        # the request finished on the decode replica
        m = gw._models["m"]
        assert h.replica == "m#1"
        # pages moved = the prompt's content pages (20 tokens / 16-token
        # pages = 2); bytes = pages × page_bytes EXACTLY
        moved = _counter('mx_serve_page_migration_pages_total'
                         '{model="m"}') - pages0
        assert moved == 2
        assert (_counter('mx_serve_page_migration_bytes_total'
                         '{model="m"}') - bytes0
                == moved * stubs[0].page_bytes)
        # refcount handoff: the source side keeps only its prefix-cache
        # refs (the prompt's FULL pages stay warm for future prefills:
        # floor(20/16) = 1); the request itself holds no source pages
        assert stubs[0].prefix_cache.cached_pages == 1
        # decode side: the migration registered the prompt's full pages
        # there too + the request released its own refs at retire
        assert stubs[1].prefix_cache.cached_pages == 1
        for rep in m.replicas:
            assert rep.sched.idle and not rep.live
    finally:
        gw.shutdown(drain=False)
    # shutdown clears the prefix caches: every page ref returns
    for s in stubs:
        assert _free_pages(s) == s.allocator.usable_pages


def test_prefill_pool_is_not_the_submit_viability_bar():
    # prefill pool: 3 usable pages (prompt fits), decode pool: plenty —
    # the old replica-0 check would have rejected this request
    gw, _stubs = _disagg_gateway(prefill_pages=4, decode_pages=12)
    try:
        h = gw.submit("m", _prompt(20), 40)      # 4 decode-side pages
        _drive(gw, [h])
        assert h.state == "done" and len(h.tokens) == 40
    finally:
        gw.shutdown(drain=False)
    # ... and a request that fits NO decode pool is still loudly
    # rejected at submit (4 pages needed, 3 usable decode-side)
    gw2, _ = _disagg_gateway(decode_pages=4)
    try:
        with pytest.raises(PagePoolExhausted):
            gw2.submit("m", _prompt(40), 24)
    finally:
        gw2.shutdown(drain=False)


def test_page_migration_fault_falls_back_colocated_no_leak():
    gw, stubs = _disagg_gateway()
    try:
        pages0 = _counter('mx_serve_page_migration_pages_total'
                          '{model="m"}')
        injection.configure_injection("page_migration:1.0:0:1")
        h = gw.submit("m", _prompt(20), 6)
        _drive(gw, [h])
        injection.clear_injection()
        # the token run is STILL unbroken — the request finished
        # co-located on its prefill replica
        assert h.state == "done"
        assert h.tokens == list(range(20, 26))
        assert h.replica == "m#0"
        # the aborted handoff moved nothing
        assert _counter('mx_serve_page_migration_pages_total'
                        '{model="m"}') == pages0
        # NO page leak: the decode side's trial allocation rolled back
        # to a completely free pool
        assert _free_pages(stubs[1]) == stubs[1].allocator.usable_pages
        # source side holds only the prompt's full-page prefix refs
        assert stubs[0].prefix_cache.cached_pages == 1
        stubs[0].prefix_cache.clear()
        assert _free_pages(stubs[0]) == stubs[0].allocator.usable_pages
    finally:
        injection.clear_injection()
        gw.shutdown(drain=False)


def test_decode_exhausted_falls_back_colocated():
    # the decode pool fits EITHER request statically (so submit admits
    # both) but not both at once: the second migration aborts at the
    # page-exhaustion check and the prefill replica finishes that
    # request itself, co-located
    gw, _stubs = _disagg_gateway(decode_pages=6)  # 5 usable pages
    try:
        hs = [gw.submit("m", _prompt(20, seed=i), 20)  # 3 pages each
              for i in range(2)]
        _drive(gw, hs)
        for h in hs:
            assert h.state == "done"
            assert h.tokens == list(range(20, 40))
        # exactly one migrated, the other fell back to its prefill home
        assert sorted(h.replica for h in hs) == ["m#0", "m#1"]
    finally:
        gw.shutdown(drain=False)


def test_migration_feeds_decode_prefix_warmth():
    """Two identical prompts: the second request's migration lands on a
    decode replica already holding the prompt's page digests — the
    content-addressed fill made the migration idempotent."""
    gw, stubs = _disagg_gateway(n_decode=2)
    try:
        h1 = gw.submit("m", _prompt(32, seed=3), 4)
        _drive(gw, [h1])
        warm = [stubs[1 + i].prefix_cache.shared_tokens(
            _prompt(32, seed=3)) for i in range(2)]
        # exactly one warm side (a proper-prefix probe: 1 of 2 pages)
        assert sorted(warm) == [0, 16]
        h2 = gw.submit("m", _prompt(32, seed=3), 4)
        _drive(gw, [h2])
        assert h2.tokens == h1.tokens == list(range(32, 36))
        # prefix affinity routed the second migration to the warm side
        assert h2.replica == h1.replica
    finally:
        gw.shutdown(drain=False)


def test_preemption_and_tiers_preserved_under_disagg():
    """Priority preemption still works — scoped to the prefill side, so
    the victim search never lands a prefill submit on a decode
    replica."""
    gw, _stubs = _disagg_gateway(prefill_pages=9)  # 2 slots, 8 pages
    try:
        pre0 = gw.preemptions_total
        # two long-prompt lows fill the prefill replica's two slots
        lows = [gw.submit("m", _prompt(60, seed=i), 2, tenant="crawl",
                          priority="low") for i in range(2)]
        for _ in range(2):
            gw.step()
        high = gw.submit("m", _prompt(8, seed=9), 2, tenant="acme",
                         priority="high")
        _drive(gw, lows + [high])
        assert high.state == "done"
        assert {r.state for r in lows} == {"done"}
        for r in lows:                 # preempted or not, full budget
            assert len(r.tokens) == 2
        assert gw.preemptions_total >= pre0
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# elastic role-awareness (stub engines, quick)
# ---------------------------------------------------------------------------

def test_elastic_replaces_dead_decode_replica_with_decode_role():
    gw, _stubs = _disagg_gateway(n_decode=2)
    ctl = gw.enable_elastic(
        factories={"m": lambda n_pages: _StubSlots(n_pages=n_pages)},
        min_replicas=1, max_replicas=4)
    try:
        m = gw._models["m"]
        assert [r.role for r in m.replicas] == ["prefill", "decode",
                                                "decode"]
        # kill replica index 1 (a decode replica) via the chaos seam
        injection.configure_injection("replica_crash@1:1.0:0:1")
        gw.step()
        injection.clear_injection()
        roles = sorted(r.role for r in m.replicas)
        assert roles == ["decode", "decode", "prefill"]
        replaced = [r for r in m.replicas if r.index >= 3]
        assert replaced and replaced[0].role == "decode"
        # the warmed replacement never compiled a prefill program: its
        # decode-only warmup drained fully
        assert replaced[0].sched.idle
        # traffic still flows end-to-end through the repaired pod
        h = gw.submit("m", _prompt(20), 4)
        _drive(gw, [h])
        assert h.tokens == list(range(20, 24))
    finally:
        injection.clear_injection()
        gw.shutdown(drain=False)
    assert ctl is not None


def test_elastic_scale_up_adds_decode_and_floor_guards_roles():
    gw, _stubs = _disagg_gateway()
    gw.enable_elastic(
        factories={"m": lambda n_pages: _StubSlots(n_pages=n_pages)},
        min_replicas=1, max_replicas=4)
    ctl = gw._elastic
    try:
        m = gw._models["m"]
        added = ctl.scale_up("m")
        assert [r.role for r in added] == ["decode"]
        # scale-down never drains the last replica of a role: with
        # 1 prefill + 2 decode, two scale-downs leave 1+1, not 0+2
        ctl.scale_down("m", n=3)
        alive = [r for r in m.replicas if not r.draining]
        assert sorted(r.role for r in alive) == ["decode", "prefill"]
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# mixed-length trace preset (quick)
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


def test_mixed_length_trace_preset():
    loadgen = _loadgen()
    ev = loadgen.mixed_length_trace(40, "m", seed=3, long_frac=0.25,
                                    long_prompt=48)
    assert len(ev) == 40
    # seeded determinism
    ev2 = loadgen.mixed_length_trace(40, "m", seed=3, long_frac=0.25,
                                     long_prompt=48)
    assert [e.to_dict() for e in ev] == [e.to_dict() for e in ev2]
    tenants = {e.tenant for e in ev}
    assert tenants == {"archive", "chat"}
    longs = [e for e in ev if e.tenant == "archive"]
    chats = [e for e in ev if e.tenant == "chat"]
    assert len(longs) == 10
    # the two populations stress opposite ends: long prompts dwarf the
    # chat ones on average (the tails may brush — lognormal jitter)
    mean = lambda xs: sum(xs) / len(xs)  # noqa: E731
    assert (mean([e.prompt_len for e in longs])
            > 2 * mean([e.prompt_len for e in chats]))
    assert all(e.priority == "high" for e in chats)
    assert ev == sorted(ev, key=lambda e: e.t)


def test_mixed_length_replay_on_disagg_pod():
    """The acceptance trace end-to-end on a stub pod: every request
    completes, migrations happened, and decode-side residency exceeds
    the prefill side's (the disaggregation point)."""
    loadgen = _loadgen()
    gw, _stubs = _disagg_gateway(n_decode=2, decode_pages=24)
    try:
        ev = loadgen.mixed_length_trace(
            12, "m", seed=5, duration_s=0.3, long_prompt=48,
            long_new_range=(2, 4), chat_new_range=(2, 6))
        p0 = _counter('mx_serve_page_migration_pages_total{model="m"}')
        rep = loadgen.replay(gw, ev, VOCAB, timeout=60.0)
        assert not rep["failed"] and rep["completed"] == len(ev)
        assert _counter('mx_serve_page_migration_pages_total'
                        '{model="m"}') > p0
    finally:
        gw.shutdown(drain=False)


# ---------------------------------------------------------------------------
# real engines: migrated-page parity + the decode-ledger gate
# ---------------------------------------------------------------------------

def _spicy_net(weight_seed):
    """Non-degenerate random weights, same recipe as test_gateway.py."""
    mx.random.seed(11)
    m = gpt_tiny(vocab_size=VOCAB, max_length=64, dropout=0.0)
    m.initialize()
    r = onp.random.RandomState(weight_seed)
    for _name, p in m.collect_params().items():
        if p.shape and len(p.shape) >= 2:
            p.set_data(np.array(
                r.normal(0, 0.35, p.shape).astype("float32")))
    return m


def test_migrated_page_parity_real_engines():
    """THE acceptance gate: prefilled on replica A, decoded on replica
    B → BIT-IDENTICAL greedy tokens vs a single-replica ``role="both"``
    pod, pages/bytes audited, zero prefill families on the decode
    side, zero steady-state recompiles on BOTH sides."""
    prompts = [(_prompt(21, seed=1), 6), (_prompt(7, seed=2), 8),
               (_prompt(33, seed=3), 5)]

    # baseline: one homogeneous replica
    reg_b = serve.ModelRegistry(total_pages=40)
    reg_b.add("gpt", _spicy_net(42), max_slots=2, max_len=64)
    gw_b = serve.Gateway(reg_b)
    try:
        base = []
        for p, n in prompts:
            h = gw_b.submit("gpt", p, n)
            gw_b._drive_until([h], timeout=120.0)
            base.append(list(h.tokens))
    finally:
        gw_b.shutdown(drain=False)

    # disaggregated pod: same weights, 1 prefill + 1 decode replica
    reg = serve.ModelRegistry(total_pages=40)
    reg.add("gpt", _spicy_net(42), prefill_replicas=1,
            decode_replicas=1, max_slots=2, max_len=64)
    gw = serve.Gateway(reg)
    try:
        m = gw._models["gpt"]
        assert [r.role for r in m.replicas] == ["prefill", "decode"]
        # the decode side got the bigger page cut (the disagg point:
        # HBM that would fund prefill working sets funds pages)
        assert (m.replicas[1].slots.allocator.usable_pages
                > m.replicas[0].slots.allocator.usable_pages)
        p0 = _counter('mx_serve_page_migration_pages_total'
                      '{model="gpt"}')
        b0 = _counter('mx_serve_page_migration_bytes_total'
                      '{model="gpt"}')
        got = []
        for p, n in prompts:
            h = gw.submit("gpt", p, n)
            gw._drive_until([h], timeout=120.0)
            assert h.replica == "gpt#1"        # finished on decode side
            got.append(list(h.tokens))
        # BIT-IDENTICAL greedy parity across the migration
        assert got == base
        # zero steady-state recompiles on BOTH sides: the first pass
        # warmed every prefill chunk bucket; a second pass of fresh
        # prompts at the SAME lengths (and its migrations) must not
        # compile anything new anywhere
        programs = gw.xla_program_counts(per_replica=True)
        for i, (p, n) in enumerate(prompts):
            h = gw.submit("gpt", _prompt(p.size, seed=50 + i), n)
            gw._drive_until([h], timeout=120.0)
            assert h.state == "done"
        assert gw.xla_program_counts(per_replica=True) == programs
        moved = _counter('mx_serve_page_migration_pages_total'
                         '{model="gpt"}') - p0
        # both passes migrated every request's content pages
        assert moved == 2 * sum(-(-p.size // 16) for p, _ in prompts)
        # the byte audit: EXACTLY pages moved × per-page pool bytes
        assert (_counter('mx_serve_page_migration_bytes_total'
                         '{model="gpt"}') - b0
                == moved * m.replicas[0].slots.page_bytes)
        # the ledger gate: the decode replica NEVER compiled a prefill
        # program (live program caches + instrumented compile ledger)
        assert disagg.decode_prefill_families(gw, "gpt") == {}
        assert m.replicas[1].slots._prefill_jit is None
        assert m.replicas[1].slots._decode_jit is not None
    finally:
        gw.shutdown(drain=False)
