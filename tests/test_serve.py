"""mx.serve — continuous batching over the PAGED KV cache (ISSUE 4 + 6).

Three layers of coverage, all deterministic on CPU:

- host-only unit tests for the paging machinery (`PageAllocator`,
  `PrefixCache`): alloc/free/refcount, loud `PagePoolExhausted` OOM, and
  the no-silent-eviction-of-shared-pages contract;
- scheduler-logic tests against a stub slot decoder (pure host
  arithmetic, no XLA compile — the `quick`-marked ones): backpressure,
  remaining-chunk SJF, deadlines, drain semantics, the fault seam;
- engine tests running a tiny 2-layer GPT through the real compiled
  paged programs: per-request parity with one-at-a-time
  `GPTDecoder.generate` WITH paging + shared-prefix reuse + chunked
  prefill all active, int8-KV parity within tolerance, slot/page reuse
  after EOS retirement, and the recompile-count gate (program count
  constant across 3× more requests than slots; the traced twin lives in
  test_tracing.py).
"""
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import np, serve
from incubator_mxnet_tpu.models.decoding import GPTDecoder
from incubator_mxnet_tpu.models.gpt import gpt_tiny
from incubator_mxnet_tpu.serve.engine import (PageAllocator,
                                              PagePoolExhausted,
                                              PrefixCache)
from incubator_mxnet_tpu.serve.scheduler import (DeadlineExceeded,
                                                 EngineClosed, QueueFull,
                                                 Scheduler)

VOCAB = 97


# ---------------------------------------------------------------------------
# paging machinery — host-only unit tests (quick)
# ---------------------------------------------------------------------------

def test_page_allocator_alloc_free_refcount():
    a = PageAllocator(n_pages=9, page_tokens=16)        # 8 usable, 0 = trash
    assert a.usable_pages == 8 and a.free_pages == 8 and a.used_pages == 0
    pages = a.alloc(3)
    assert len(pages) == 3 and 0 not in pages           # trash never handed out
    assert a.free_pages == 5 and a.used_pages == 3
    # sharing: a second holder increfs; the first decref keeps the page
    a.incref(pages[:1])
    a.decref(pages[:1])
    assert a.free_pages == 5                            # still referenced
    a.decref(pages)
    assert a.free_pages == 8 and a.used_pages == 0
    # double free is loud
    with pytest.raises(RuntimeError):
        a.decref(pages[:1])
    # incref on a free page is loud (shared page dropped while mapped)
    with pytest.raises(RuntimeError):
        a.incref([pages[0]])


def test_page_allocator_oom_loud():
    a = PageAllocator(n_pages=5, page_tokens=8)         # 4 usable
    held = a.alloc(3)
    with pytest.raises(PagePoolExhausted) as ei:
        a.alloc(2)
    assert "never" in str(ei.value)                     # no silent eviction
    from incubator_mxnet_tpu.fault.retry import classify_exception

    assert classify_exception(ei.value) in ("retryable", "fatal")
    a.decref(held)
    assert len(a.alloc(4)) == 4


def test_prefix_cache_register_lookup_evict():
    a = PageAllocator(n_pages=17, page_tokens=4)        # 16 usable
    cache = PrefixCache(a)
    prompt = onp.arange(11, dtype=onp.int32)            # 2 full pages + tail
    pages = a.alloc(3)
    cache.register(prompt, pages)                       # entries for pages 1,2
    assert len(cache) == 2
    # longest page-aligned PROPER prefix: 8 of 11 tokens
    tokens, shared = cache.lookup(prompt)
    assert tokens == 8 and shared == pages[:2]
    # a prompt extending the same prefix matches it too
    longer = onp.concatenate([prompt[:8], onp.full(6, 90, onp.int32)])
    tokens2, shared2 = cache.lookup(longer)
    assert tokens2 == 8 and shared2 == pages[:2]
    # an identical-length prompt with a different first page misses
    other = onp.concatenate([onp.full(4, 91, onp.int32), prompt[4:]])
    assert cache.lookup(other)[0] == 0
    # the request retires: ITS refs drop, the cache's refs keep pages live
    a.decref(pages)
    assert a.used_pages == 2                            # page 3 freed
    # eviction drops cache refs only — a page shared into a live request
    # survives eviction (refcount stays positive, page NOT reused)
    t, sp = cache.lookup(prompt)
    a.incref(sp)                                        # "live request"
    cache.evict_unused(a.usable_pages)                  # evict everything
    assert len(cache) == 0
    assert a.refcount(sp[0]) == 1 and a.refcount(sp[1]) == 1
    free_before = a.free_pages
    got = a.alloc(free_before)
    assert not set(got) & set(sp)                       # never reused
    a.decref(got)
    a.decref(sp)
    assert a.free_pages == a.usable_pages


def test_prefix_cache_leaves_one_token_for_compute():
    """A fully page-aligned identical prompt still prefills >= 1 token —
    the final token's forward pass produces the first sampled token."""
    a = PageAllocator(n_pages=9, page_tokens=4)
    cache = PrefixCache(a)
    prompt = onp.arange(8, dtype=onp.int32)             # exactly 2 pages
    pages = a.alloc(2)
    cache.register(prompt, pages)                       # both pages cached
    tokens, shared = cache.lookup(prompt)
    assert tokens == 4 and shared == pages[:1]          # proper prefix only


# ---------------------------------------------------------------------------
# scheduler logic against a stub decoder (no XLA, quick)
# ---------------------------------------------------------------------------

class _StubSlots:
    """Paged-interface stand-in: pure host arithmetic over a REAL
    allocator/prefix cache (host-only classes). The final prefill chunk
    emits the prompt's length as the first token, decode increments —
    fully deterministic host math."""

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
        self.chunks = []                  # (slot, t_start, n) per chunk

    def set_slot_pages(self, slot, pages):
        pass

    def clear_slot(self, slot):
        pass

    def prefill_chunk_step(self, slot, chunk_tokens, t_start, key,
                           temperature=1.0):
        n = len(chunk_tokens)
        self.chunks.append((slot, int(t_start), n))
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


def test_queue_backpressure_raises():
    sched = Scheduler(_StubSlots(max_slots=1), max_queue=2)
    sched.submit(_prompt(4), 4)
    sched.submit(_prompt(5), 4)
    with pytest.raises(QueueFull) as ei:
        sched.submit(_prompt(6), 4)
    assert "capacity" in str(ei.value)
    # backpressure classifies as retryable: front-ends can reuse the
    # framework RetryPolicy unchanged
    from incubator_mxnet_tpu.fault.retry import classify_exception

    assert classify_exception(ei.value) == "retryable"


def test_submit_validation():
    sched = Scheduler(_StubSlots(max_len=16), max_queue=4)
    with pytest.raises(ValueError):
        sched.submit(_prompt(10), 8)       # 18 > max_len 16
    with pytest.raises(ValueError):
        sched.submit(onp.zeros((0,), onp.int32), 4)
    with pytest.raises(ValueError):
        sched.submit(_prompt(4), 0)
    with pytest.raises(ValueError):
        Scheduler(_StubSlots(), policy="weird")


def test_submit_page_budget_loud():
    """A request that could never fit the pool is rejected at submit
    with the loud PagePoolExhausted, not deferred forever."""
    stub = _StubSlots(max_slots=2, max_len=64, page_tokens=16)
    stub.allocator = PageAllocator(3, 16)   # 2 usable pages = 32 tokens
    sched = Scheduler(stub, max_queue=4)
    with pytest.raises(PagePoolExhausted):
        sched.submit(_prompt(30), 20)       # needs 4 pages, pool has 2
    sched.submit(_prompt(10), 10)           # 2 pages: fits


def test_sjf_policy_admits_shortest_first():
    sched = Scheduler(_StubSlots(max_slots=1), policy="sjf", max_queue=8)
    long = sched.submit(_prompt(12), 6)
    short = sched.submit(_prompt(3), 6)
    mid = sched.submit(_prompt(7), 6)
    sched.step()
    assert short.state == "running" and long.state == "queued"
    assert mid.state == "queued"
    # fifo keeps arrival order
    sched2 = Scheduler(_StubSlots(max_slots=1), policy="fifo", max_queue=8)
    a = sched2.submit(_prompt(12), 6)
    b = sched2.submit(_prompt(3), 6)
    sched2.step()
    assert a.state == "running" and b.state == "queued"


def test_sjf_orders_by_remaining_prefill_chunks():
    """ISSUE 6 accounting fix: a LONG prompt whose prefix is cached
    needs fewer remaining chunks than a shorter cold prompt — SJF must
    admit it first."""
    stub = _StubSlots(max_slots=1, max_len=64, page_tokens=8,
                      prefill_chunk=8)
    sched = Scheduler(stub, policy="sjf", max_queue=8)
    long_prompt = _prompt(33, seed=3)       # 5 chunks cold
    short_prompt = _prompt(17, seed=4)      # 3 chunks cold
    # cache the long prompt's first 4 pages: remaining = 1 chunk
    pages = stub.allocator.alloc(4)
    stub.prefix_cache.register(long_prompt[:32], pages)
    h_long = sched.submit(long_prompt, 5)
    h_short = sched.submit(short_prompt, 5)
    sched.step()
    assert h_long.state == "running" and h_short.state == "queued"
    assert h_long.shared_tokens == 32
    # and without the cache entry, plain shortest-first still wins
    stub2 = _StubSlots(max_slots=1, max_len=64, page_tokens=8,
                       prefill_chunk=8)
    sched2 = Scheduler(stub2, policy="sjf", max_queue=8)
    a = sched2.submit(_prompt(33, seed=3), 2)
    b = sched2.submit(_prompt(17, seed=4), 2)
    sched2.step()
    assert b.state == "running" and a.state == "queued"


def test_chunked_prefill_interleaves_with_decode():
    """A long prompt prefills across several steps; an already-running
    request keeps producing a token EVERY step in between (the TTFT-p99
    fix chunking exists for)."""
    stub = _StubSlots(max_slots=2, max_len=64, page_tokens=8,
                      prefill_chunk=8)
    sched = Scheduler(stub, max_queue=8)
    runner = sched.submit(_prompt(4), 20)
    sched.step()      # admit + single-chunk prefill + first decode step
    assert runner.state == "running" and len(runner.tokens) == 2
    long_req = sched.submit(_prompt(33, seed=5), 2)   # 5 chunks
    produced_during_prefill = []
    for _ in range(4):                      # chunks 1..4: still prefilling
        before = len(runner.tokens)
        sched.step()
        produced_during_prefill.append(len(runner.tokens) - before)
        assert long_req.first_token_t is None
    assert all(n == 1 for n in produced_during_prefill)
    sched.step()                            # final chunk: first token
    assert long_req.first_token_t is not None
    assert long_req.tokens[0] == 33         # stub: prompt length
    assert len(stub.chunks) >= 5 + 1


def test_deadline_expiry_classifies_retryable():
    sched = Scheduler(_StubSlots(max_slots=1), max_queue=8)
    req = sched.submit(_prompt(4), 4, deadline_s=0.0)
    time.sleep(0.005)
    sched.step()
    assert req.state == "failed"
    with pytest.raises(DeadlineExceeded):
        req.result()
    assert req.error_class == "retryable"
    # a mid-decode deadline frees the slot for the next request
    r2 = sched.submit(_prompt(4), 50, deadline_s=0.02)
    sched.step()
    assert r2.state == "running"
    time.sleep(0.03)
    sched.step()
    assert r2.state == "failed" and sched.n_active == 0
    # pages went back with the slot
    assert sched.slots.allocator.used_pages == 0


def test_drain_semantics_scheduler():
    sched = Scheduler(_StubSlots(max_slots=1), max_queue=8)
    running = sched.submit(_prompt(4), 3)
    queued = sched.submit(_prompt(5), 3)
    sched.step()
    assert running.state == "running"
    # drain: queued (never admitted) fails loudly, running survives ...
    sched.close(drain=True)
    assert queued.state == "failed"
    with pytest.raises(EngineClosed):
        queued.result()
    with pytest.raises(EngineClosed):
        sched.submit(_prompt(3), 2)
    while not running.done:
        sched.step()
    assert running.result() == [4, 5, 6]   # stub: len, +1, +1
    # ... while drain=False also fails the in-flight slots
    sched2 = Scheduler(_StubSlots(max_slots=1), max_queue=8)
    r = sched2.submit(_prompt(4), 10)
    sched2.step()
    sched2.close(drain=False)
    assert r.state == "failed" and sched2.n_active == 0
    with pytest.raises(EngineClosed):
        r.result()


def test_eos_retirement_and_eviction_metrics():
    from incubator_mxnet_tpu.telemetry import registry

    sched = Scheduler(_StubSlots(max_slots=2), max_queue=8, eos_id=6)
    before = registry.counter(
        "mx_serve_evictions_total",
        "slots freed (EOS / length / deadline / shutdown)").value
    # stub emits len, len+1, ...: a 4-prompt hits eos_id=6 on token 3
    req = sched.submit(_prompt(4), 10)
    while not req.done:
        sched.step()
    assert req.result() == [4, 5, 6]       # truncated AT the eos token
    assert sched.n_active == 0             # slot freed mid-flight
    after = registry.counter(
        "mx_serve_evictions_total",
        "slots freed (EOS / length / deadline / shutdown)").value
    assert after == before + 1


def test_serve_step_fault_seam():
    from incubator_mxnet_tpu import fault

    sched = Scheduler(_StubSlots(), max_queue=4)
    fault.configure_injection("serve_step:1.0:0:1")
    try:
        with pytest.raises(fault.FaultInjected):
            sched.step()
    finally:
        fault.clear_injection()
    sched.step()                           # limit=1: next step is clean


# ---------------------------------------------------------------------------
# real engine over a tiny 2-layer GPT (compiled paged programs)
# ---------------------------------------------------------------------------

def _spicy_net():
    """Spicy random weights (non-degenerate logits) so greedy parity
    exercises token-dependent paths — same recipe as test_gpt.py."""
    mx.random.seed(11)
    m = gpt_tiny(vocab_size=VOCAB, max_length=64, dropout=0.0)
    m.initialize()
    r = onp.random.RandomState(42)
    for _name, p in m.collect_params().items():
        if p.shape and len(p.shape) >= 2:
            p.set_data(np.array(
                r.normal(0, 0.35, p.shape).astype("float32")))
    return m


@pytest.fixture(scope="module")
def net():
    return _spicy_net()


@pytest.fixture(scope="module")
def ref_dec(net):
    return GPTDecoder(net)


@pytest.fixture(scope="module")
def eng(net):
    """Shared engine: 3 slots so a dozen requests exercise slot reuse."""
    e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32)
    yield e
    if not e.closed:
        e.shutdown(drain=False)


def _mixed_requests(n, seed=0, lo=3, hi=18, budget_lo=2, budget_hi=12):
    r = onp.random.RandomState(seed)
    prompts = [r.randint(0, VOCAB, (int(r.randint(lo, hi)),))
               .astype(onp.int32) for _ in range(n)]
    budgets = [int(r.randint(budget_lo, budget_hi)) for _ in range(n)]
    return prompts, budgets


def test_serve_matches_one_at_a_time_and_never_recompiles(eng, ref_dec):
    """The acceptance gate: 3× more requests than slots, varied prompt
    lengths and budgets, all flowing through paged slot reuse —
    per-request output identical to one-at-a-time GPTDecoder.generate,
    with ZERO steady-state recompiles (the traced twin of this gate is
    test_tracing.test_real_engine_traced_requests_and_recompile_gate)."""
    prompts, budgets = _mixed_requests(9, seed=1)
    # warmup: one prompt per chunk bucket in play (16/32/64) + decode
    eng.generate(_prompt(5, seed=9), 3)
    eng.generate(onp.resize(_prompt(5, seed=9), 20), 3)
    eng.generate(onp.resize(_prompt(5, seed=9), 40), 3)
    warm_count = eng.xla_program_count()
    assert warm_count >= 2                 # ≥1 chunk bucket + decode

    handles = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
    eng._drive_until(handles)
    for p, b, h in zip(prompts, budgets, handles):
        ref = ref_dec.generate(p[None, :], b).asnumpy()[0]
        got = onp.concatenate([p, onp.asarray(h.result(), onp.int32)])
        onp.testing.assert_array_equal(got, ref)
    # steady state: same program count, no matter how many requests
    assert eng.xla_program_count() == warm_count


def test_paged_prefix_reuse_and_chunking_parity(net, ref_dec):
    """The tentpole end-to-end: small pages, multi-chunk prefill, and a
    SHARED system prompt across requests — outputs stay bit-identical to
    the unpaged reference while the prefix cache takes real hits and the
    program count stays flat."""
    from incubator_mxnet_tpu.telemetry import registry

    e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32,
                          page_tokens=8, prefill_chunk=16)
    try:
        system = _prompt(24, seed=42)               # 3 shared pages
        tails = [_prompt(int(onp.random.RandomState(i).randint(2, 8)),
                         seed=100 + i) for i in range(8)]
        prompts = [onp.concatenate([system, t]) for t in tails]
        # warm the chunk buckets (8 and 16) + decode out of the gate
        e.generate(prompts[0][:19], 2)
        e.generate(prompts[0][:16], 2)
        warm = e.xla_program_count()
        hits0 = registry.counter("mx_serve_prefix_hits_total").value
        chunks0 = registry.counter("mx_serve_prefill_chunks_total").value
        handles = [e.submit(p, 6) for p in prompts]
        e._drive_until(handles)
        for p, h in zip(prompts, handles):
            ref = ref_dec.generate(p[None, :], 6).asnumpy()[0]
            got = onp.concatenate([p, onp.asarray(h.result(), onp.int32)])
            onp.testing.assert_array_equal(got, ref)
        hits = registry.counter("mx_serve_prefix_hits_total").value - hits0
        chunks = registry.counter(
            "mx_serve_prefill_chunks_total").value - chunks0
        assert hits >= 4                   # later waves reuse the prefix
        assert chunks >= len(prompts)      # chunked prefill really ran
        assert e.xla_program_count() == warm
        # paged accounting: shared pages counted once, gauge is live
        rep = registry.report()
        assert 0 < rep["mx_serve_page_occupancy"]["value"] <= 1
    finally:
        e.shutdown(drain=False)
    # a drained engine returns every page (cache cleared at shutdown)
    assert e._sched.slots.allocator.used_pages == 0


def test_int8_kv_parity_within_tolerance(net, ref_dec):
    """MXNET_SERVE_KV_DTYPE=int8 equivalent: half the resident KV bytes,
    greedy outputs within tolerance — first token EXACT for single-chunk
    prompts (the chunk attends to its own pre-quantization K/V), and the
    divergence-free prefix covers most of each generation."""
    e8 = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32,
                           kv_dtype="int8")
    efp = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32)
    try:
        prompts, budgets = _mixed_requests(9, seed=1)
        match, total = 0, 0
        for p, b in zip(prompts, budgets):
            out = e8.generate(p, b)[p.size:]
            ref = ref_dec.generate(p[None, :], b).asnumpy()[0][p.size:]
            assert out[0] == ref[0]        # single-chunk first token exact
            k = 0
            for x, y in zip(out, ref):
                if x != y:
                    break
                k += 1
            match += k
            total += len(ref)
        assert match / total >= 0.5, f"int8 drift too large: {match}/{total}"
        # the headline economics: ~4x fewer KV bytes resident per slot
        efp.generate(prompts[0], 2)        # materialize the fp pool
        assert e8.kv_bytes_per_slot < 0.3 * efp.kv_bytes_per_slot
    finally:
        e8.shutdown(drain=False)
        efp.shutdown(drain=False)


def test_out_of_order_completion(eng, ref_dec):
    """An earlier-submitted long request must not block (or corrupt) a
    later short one — completion is out of order, results per-request."""
    p_long, p_short = _prompt(6, seed=2), _prompt(9, seed=3)
    h_long = eng.submit(p_long, 14)
    h_short = eng.submit(p_short, 2)
    eng._drive_until([h_long, h_short])
    assert h_short.finish_t < h_long.finish_t
    for p, b, h in [(p_long, 14, h_long), (p_short, 2, h_short)]:
        ref = ref_dec.generate(p[None, :], b).asnumpy()[0]
        got = onp.concatenate([p, onp.asarray(h.result(), onp.int32)])
        onp.testing.assert_array_equal(got, ref)


def test_slot_reuse_after_eos_retirement(eng, ref_dec):
    """EOS retires a slot mid-flight; the freed slot (and its pages)
    serve the next queued request, and stale cache rows never leak."""
    prompts, _ = _mixed_requests(6, seed=4)
    budget = 10
    # pick a real EOS: the token the reference generates 3rd for the
    # first prompt — that request must stop early, the rest run free
    ref0 = ref_dec.generate(prompts[0][None, :], budget).asnumpy()[0]
    eos = int(ref0[prompts[0].size + 2])
    handles = [eng.submit(p, budget, eos_id=eos) for p in prompts]
    eng._drive_until(handles)
    for p, h in zip(prompts, handles):
        ref = ref_dec.generate(p[None, :], budget).asnumpy()[0]
        new = list(ref[p.size:])
        if eos in new:                     # truncated AT first eos
            new = new[:new.index(eos) + 1]
        assert h.result() == [int(t) for t in new]
    # the tagged request really did stop AT its eos, mid-budget
    assert handles[0].tokens[-1] == eos
    assert len(handles[0].tokens) <= 3
    assert eng.n_active == 0


def test_streaming_iter_tokens_ordering(eng, ref_dec):
    p = _prompt(7, seed=5)
    h = eng.submit(p, 8)
    streamed = list(eng.iter_tokens(h))
    ref = ref_dec.generate(p[None, :], 8).asnumpy()[0]
    assert streamed == [int(t) for t in ref[p.size:]]
    assert streamed == h.result()


def test_driver_thread_serves_client_submits(eng, ref_dec):
    """A background driver owns the step loop while this (client) thread
    only submits and streams — the ISSUE's threading contract."""
    eng.start()
    try:
        prompts, budgets = _mixed_requests(5, seed=6)
        handles = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        for h in handles:
            assert h.wait(timeout=120.0), h.state
        for p, b, h in zip(prompts, budgets, handles):
            ref = ref_dec.generate(p[None, :], b).asnumpy()[0]
            got = onp.concatenate([p, onp.asarray(h.result(), onp.int32)])
            onp.testing.assert_array_equal(got, ref)
    finally:
        eng.stop()


def _failing_steps(e, monkeypatch, n_failures):
    """`e.step` raises on its first `n_failures` calls (None: on every
    call), then runs as it is."""
    real, calls = e.step, []

    def step():
        calls.append(None)
        if n_failures is None or len(calls) <= n_failures:
            raise RuntimeError("planted step fault")
        return real()

    monkeypatch.setattr(e, "step", step)
    return calls


def test_driver_survives_transient_step_failures(net, ref_dec, monkeypatch,
                                                 caplog):
    """Two consecutive failures of `step()` are logged and retried: the
    driver thread stays alive and serves the request."""
    e = serve.ServeEngine(net, max_slots=2, max_len=64, max_queue=8)
    calls = _failing_steps(e, monkeypatch, 2)
    prompts, _ = _mixed_requests(1, seed=8)
    with caplog.at_level("ERROR", logger="incubator_mxnet_tpu.serve"):
        e.start()
        try:
            h = e.submit(prompts[0], 4)
            assert h.wait(timeout=120.0), h.state
            assert e._driver_running()
        finally:
            e.stop()
    assert len(calls) > 2
    ref = ref_dec.generate(prompts[0][None, :], 4).asnumpy()[0]
    got = onp.concatenate([prompts[0], onp.asarray(h.result(), onp.int32)])
    onp.testing.assert_array_equal(got, ref)
    failed = [r.getMessage() for r in caplog.records
              if "step failed" in r.getMessage()]
    assert len(failed) == 2 and "planted step fault" in failed[0]
    assert not any("stopping after" in r.getMessage()
                   for r in caplog.records)


def test_driver_stops_after_three_consecutive_step_failures(net, monkeypatch,
                                                            caplog):
    """A `step()` that fails every time stops the driver after three
    tries, with the log line that says so: it does not spin."""
    e = serve.ServeEngine(net, max_slots=2, max_len=64, max_queue=8)
    calls = _failing_steps(e, monkeypatch, None)
    with caplog.at_level("ERROR", logger="incubator_mxnet_tpu.serve"):
        e.start()
        e._driver.join(timeout=30.0)
    assert not e._driver_running()
    assert len(calls) == 3
    assert any("stopping after 3 consecutive step failures" in r.getMessage()
               for r in caplog.records)


def test_serve_telemetry_series(eng):
    from incubator_mxnet_tpu.telemetry import registry

    rep = registry.report()
    assert rep["mx_serve_ttft_seconds"]["count"] > 0
    assert rep["mx_serve_ttft_seconds"]["min"] > 0
    assert rep["mx_serve_tokens_total"]["value"] > 0
    assert rep["mx_serve_evictions_total"]["value"] > 0
    assert "mx_serve_queue_depth" in rep
    assert "mx_serve_slot_occupancy" in rep
    # ISSUE 6 series: paged allocation + chunked prefill accounting
    assert "mx_serve_page_occupancy" in rep
    assert rep["mx_serve_prefill_chunks_total"]["value"] > 0
    assert "mx_serve_prefix_hits_total" in rep
    # bucketed prefill accounts its padding waste
    assert rep["mx_decode_bucket_pad_tokens_total"]["value"] > 0


def test_engine_drain_finishes_running_rejects_new(net, ref_dec):
    """shutdown(drain=True): requests in slots finish completely (also
    mid-prefill ones), the never-admitted queue and new submits are
    rejected loudly."""
    e = serve.ServeEngine(net, max_slots=2, max_len=64, max_queue=8)
    prompts, _ = _mixed_requests(3, seed=7)
    h1 = e.submit(prompts[0], 8)
    h2 = e.submit(prompts[1], 8)
    h3 = e.submit(prompts[2], 8)           # stays queued: only 2 slots
    e.step()                               # admit h1/h2, first decode
    assert h3.state == "queued"
    e.shutdown(drain=True)
    assert h1.done and h2.done and h1.error is None and h2.error is None
    for p, h in [(prompts[0], h1), (prompts[1], h2)]:
        ref = ref_dec.generate(p[None, :], 8).asnumpy()[0]
        got = onp.concatenate([p, onp.asarray(h.result(), onp.int32)])
        onp.testing.assert_array_equal(got, ref)
    with pytest.raises(EngineClosed):
        h3.result()
    with pytest.raises(EngineClosed):
        e.submit(prompts[0], 4)


# -- the decode step in flight (ISSUE 30), through `ServeEngine` ---------------

def _serial(engine):
    """The serial order of work on the same programs: a `decode_step` that
    hands back host tokens leaves nothing in flight."""
    slots = engine._sched.slots
    inner = slots.decode_step
    slots.decode_step = lambda *a: onp.asarray(inner(*a))


def _references(ref_dec, prompts, budgets):
    return [[int(t) for t in ref_dec.generate(p[None, :], b).asnumpy()[0]
             [p.size:]] for p, b in zip(prompts, budgets)]


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_manual_step_loop_until_idle_delivers_every_token(net, kv_dtype):
    """`while eng.step()`: a step that only fetches the step in flight still
    counts as progress, so the loop does not stop a token short; and the
    tokens are those of an engine that fetches every step before the next
    (a `decode_step` handing back host tokens: the serial order)."""
    prompts, budgets = _mixed_requests(7, seed=11)
    outs = []
    for serial in (False, True):
        e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32,
                              kv_dtype=kv_dtype)
        slots = e._sched.slots
        if serial:
            _serial(e)
        handles = [e.submit(p, b) for p, b in zip(prompts, budgets)]
        in_flight = 0
        while e.step():
            in_flight += e._sched._flight is not None
        assert all(h.done for h in handles) and e.n_active == 0
        assert (in_flight > 0) == (not serial)
        outs.append([h.result() for h in handles])
        assert [len(o) for o in outs[-1]] == budgets
        e.shutdown(drain=False)
        assert slots.allocator.used_pages == 0
    assert outs[0] == outs[1]


def test_sampled_tokens_do_not_depend_on_when_they_are_fetched(net):
    """Sampling at a fixed seed: the key counter is consumed chunk by chunk
    and launch by launch in the serial order's sequence, so a step in
    flight changes no sampled token."""
    prompts, budgets = _mixed_requests(7, seed=12)
    outs, keys = [], []
    for serial in (False, True):
        e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32,
                              do_sample=True, top_k=5, temperature=0.9,
                              seed=17)
        if serial:
            _serial(e)
        handles = [e.submit(p, b) for p, b in zip(prompts, budgets)]
        e._drive_until(handles)
        outs.append([h.result() for h in handles])
        keys.append(e._sched._key_ctr)
        e.shutdown(drain=False)
    assert outs[0] == outs[1] and keys[0] == keys[1]
    assert [len(o) for o in outs[0]] == budgets


def test_shutdown_drain_with_a_step_in_flight(net, ref_dec):
    """`shutdown(drain=True)` on a hand-stepped engine whose only request's
    last token is still on the device: delivered, not dropped."""
    e = serve.ServeEngine(net, max_slots=2, max_len=64, max_queue=8)
    p = _prompt(6, seed=3)
    h = e.submit(p, 4)
    while not (e._sched._flight is not None and h.slot is None):
        assert e.step()
    assert not h.done and e.n_active == 1      # owed, though no slot is held
    e.shutdown(drain=True)
    assert h.result() == _references(ref_dec, [p], [4])[0]


def test_eos_overshoot_through_the_engine(eng, ref_dec):
    """An EOS ends a request a step after the device made it: the row
    already launched is dropped and counted, and no token follows the EOS."""
    from incubator_mxnet_tpu.serve.scheduler import OVERSHOOT_ROWS

    p = _prompt(9, seed=21)
    ref = _references(ref_dec, [p], [10])[0]
    at = next(i for i in range(1, 9) if ref[i] not in ref[:i])
    before = OVERSHOOT_ROWS.value
    h = eng.submit(p, 10, eos_id=ref[at])
    eng._drive_until([h])
    assert h.result() == ref[:at + 1]
    # the request is over; the step launched before its EOS was seen is not
    assert not eng._sched.idle and eng.step() is True
    assert OVERSHOOT_ROWS.value == before + 1
    assert eng.n_active == 0 and eng._sched.idle


@pytest.mark.slow
def test_bench_gpt_serve_contract():
    """The bench lands real numbers under the loud-failure contract:
    nonzero tokens/s and TTFT percentiles, occupancy from the registry
    (reduced trace; the committed extras run the full 32-request one)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        import bench
    finally:
        sys.path.pop(0)

    tok_s, p50, p99, occ = bench.bench_gpt_serve(
        requests=6, max_slots=3, prompt_max=24, new_max=16,
        mean_interarrival_s=0.01)
    assert tok_s > 0
    assert p99 >= p50 > 0
    assert 0 < occ <= 1


@pytest.mark.slow
def test_bench_gpt_serve_prefix_contract():
    """Reduced shared-prefix bench: reuse beats the cold path and the
    hit-rate/occupancy extras come back sane (the committed extras run
    the full workload)."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        import bench
    finally:
        sys.path.pop(0)

    res = bench.bench_gpt_serve_prefix(requests=8, max_slots=2,
                                       prefix_len=96, tail_max=8,
                                       new_max=6)
    assert res["reuse_tokens_s"] > 0 and res["base_tokens_s"] > 0
    assert res["hit_rate"] > 0
    assert res["kv_bytes_per_slot"] > 0


# ---------------------------------------------------------------------------
# speculative decoding + per-layer pool layout (ISSUE 11)
# ---------------------------------------------------------------------------

def test_ngram_proposer_unit():
    """Host n-gram drafting: longest-suffix continuation lookup with a
    repeat-last fallback, always exactly k tokens."""
    from incubator_mxnet_tpu.models.decoding import NgramProposer

    p = NgramProposer(3, max_ngram=3)
    # the suffix [7, 8] occurred earlier, continued by [9, 1, 2]
    seq = onp.array([7, 8, 9, 1, 2, 7, 8], onp.int32)
    assert list(p.propose(seq)) == [9, 1, 2]
    # the suffix [5, 6] recurs with a full continuation window
    seq = onp.array([5, 6, 1, 5, 6], onp.int32)
    assert list(p.propose(seq)) == [1, 5, 6]
    # a short continuation pads with its own last token
    seq = onp.array([9, 5, 6, 5, 6], onp.int32)
    assert list(p.propose(seq)) == [5, 6, 6]
    # no suffix recurs: repeat the last token
    seq = onp.array([1, 2, 3], onp.int32)
    assert list(p.propose(seq)) == [3, 3, 3]
    with pytest.raises(ValueError):
        NgramProposer(0)


def test_spec_engine_validation_and_env_knobs(net, monkeypatch):
    """spec_k rides MXNET_SERVE_SPEC_K; sampling and an undersized or
    vocab-mismatched draft model fail loudly at construction."""
    from incubator_mxnet_tpu.serve.engine import SlotDecoder

    monkeypatch.setenv("MXNET_SERVE_SPEC_K", "2")
    s = SlotDecoder(net, max_slots=2, max_len=64)
    assert s.spec_k == 2 and s.draft_kind == "ngram"
    monkeypatch.delenv("MXNET_SERVE_SPEC_K")
    s = SlotDecoder(net, max_slots=2, max_len=64)
    assert s.spec_k == 0 and s.draft_kind == "off"
    with pytest.raises(ValueError, match="greedy"):
        SlotDecoder(net, max_slots=2, max_len=64, spec_k=3,
                    do_sample=True)
    with pytest.raises(ValueError, match="spec_k"):
        SlotDecoder(net, max_slots=2, max_len=64, spec_k=-1)
    small = gpt_tiny(vocab_size=VOCAB, max_length=32, dropout=0.0)
    small.initialize()
    with pytest.raises(ValueError, match="position table"):
        SlotDecoder(net, max_slots=2, max_len=64, spec_k=3, draft=small)
    other_vocab = gpt_tiny(vocab_size=31, max_length=64, dropout=0.0)
    other_vocab.initialize()
    with pytest.raises(ValueError, match="vocab"):
        SlotDecoder(net, max_slots=2, max_len=64, spec_k=3,
                    draft=other_vocab)


def test_spec_decode_parity_ngram_and_never_recompiles(net, ref_dec):
    """The spec acceptance gate: with the n-gram draft armed, every
    request's output is token-for-token identical to non-speculative
    greedy decode, program count stays flat in steady state, and the
    drafted/accepted counters move."""
    from incubator_mxnet_tpu.telemetry import registry

    e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32,
                          spec_k=3, draft="ngram")
    try:
        drafted0 = registry.counter(
            "mx_serve_spec_drafted_tokens_total").value
        e.generate(_prompt(5, seed=9), 3)          # warm bucket + verify
        warm = e.xla_program_count()
        prompts, budgets = _mixed_requests(9, seed=1)
        handles = [e.submit(p, b) for p, b in zip(prompts, budgets)]
        e._drive_until(handles)
        for p, b, h in zip(prompts, budgets, handles):
            ref = ref_dec.generate(p[None, :], b).asnumpy()[0]
            got = onp.concatenate([p, onp.asarray(h.result(), onp.int32)])
            onp.testing.assert_array_equal(got, ref)
        assert e.xla_program_count() == warm       # zero steady-state
        st = e.spec_stats()
        assert st["k"] == 3 and st["draft"] == "ngram"
        assert st["drafted"] > 0
        drafted = registry.counter(
            "mx_serve_spec_drafted_tokens_total").value - drafted0
        assert drafted == st["drafted"]
        # the per-model acceptance gauge is exported
        rep = registry.report()
        key = 'mx_serve_spec_accept_rate{model="serve"}'
        assert key in rep
        assert rep[key]["value"] == pytest.approx(st["accept_rate"])
    finally:
        e.shutdown(drain=False)


def test_spec_self_draft_parity_and_acceptance(net, ref_dec):
    """Drafting with the target model itself must accept ~everything
    (the draft pool tracks the committed prefix exactly) while output
    stays bit-identical — the canary for draft-pool KV holes."""
    e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=32,
                          spec_k=3, draft=GPTDecoder(net))
    try:
        prompts = [_prompt(int(onp.random.RandomState(i).randint(4, 12)),
                           seed=50 + i) for i in range(6)]
        handles = [e.submit(p, 40) for p in prompts]
        e._drive_until(handles)
        for p, h in zip(prompts, handles):
            ref = ref_dec.generate(p[None, :], 40).asnumpy()[0]
            got = onp.concatenate([p, onp.asarray(h.result(), onp.int32)])
            onp.testing.assert_array_equal(got, ref)
        st = e.spec_stats()
        assert st["draft"] == "model"
        assert st["accept_rate"] > 0.9, st
    finally:
        e.shutdown(drain=False)


def test_spec_page_rollback_refcounts(net):
    """The reservation ledger under rejection pressure: after every
    step each decoding slot holds exactly the pages its committed
    position needs (rejected-suffix pages rolled back), reservations
    never exceed the free pool, and a drained engine returns every
    page."""
    e = serve.ServeEngine(net, max_slots=2, max_len=64, max_queue=32,
                          page_tokens=8, spec_k=4, draft="ngram")
    sched = e._sched
    alloc = sched.slots.allocator
    pt = sched.slots.page_tokens
    try:
        prompts, budgets = _mixed_requests(6, seed=3, budget_lo=10,
                                           budget_hi=24)
        handles = [e.submit(p, b) for p, b in zip(prompts, budgets)]
        while not all(h.done for h in handles):
            e.step()
            assert alloc.free_pages >= sched._spec_reserved_total()
            for s, req in enumerate(sched._in_slot):
                if req is None or not sched._active[s]:
                    continue
                # post-trim: pages cover the committed position exactly
                assert len(req.pages) == int(sched._pos[s]) // pt + 1
                assert req.spec_reserved >= 0
        assert sched._spec_reserved_total() == 0
    finally:
        e.shutdown(drain=False)
    assert alloc.used_pages == 0                   # cache cleared too


def _decode_lowering(slots, platform="tpu"):
    """The decode program's lowering for `platform`, from shapes (nothing
    compiles for a chip and nothing runs): one call whatever the family
    and the page format."""
    import jax
    import jax.numpy as jnp

    slots._ensure_pool()
    S, P = slots.max_slots, slots.pages_per_slot
    sds = jax.ShapeDtypeStruct
    return slots._build_decode().trace(
        slots._dec._params, slots._pools,
        sds((S, P), jnp.int32), sds((S,), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        sds((S,), jnp.bool_),
        jax.random.PRNGKey(0), sds((S,), jnp.float32),
        top_k=None, do_sample=False).lower(
        lowering_platforms=(platform,)).as_text()


def _donated_args(text):
    import re

    args = re.search(r"@main\((.*?)\) ->", text, re.S).group(1)
    return [a for a in args.split("%arg")
            if "tf.aliasing_output" in a or "jax.buffer_donor" in a]


def _eva_slots(n_pages):
    import jax.numpy as jnp

    from incubator_mxnet_tpu.models.evabyte import (EvaByteConfig,
                                                    EvaByteDecoder)
    from incubator_mxnet_tpu.serve.eva import EvaSlotDecoder

    cfg = EvaByteConfig(num_hidden_layers=2, hidden_size=256,
                        num_attention_heads=2, intermediate_size=64,
                        vocab_size=8, num_pred_heads=1, window_size=64,
                        chunk_size=4, max_position_embeddings=256)
    top, layer = cfg.leaf_shapes()
    params = {n: jnp.zeros(s) for n, s in top.items()}
    params["layers"] = [{n: jnp.zeros(s) for n, s in layer.items()}
                        for _ in range(2)]
    return EvaSlotDecoder(EvaByteDecoder(cfg, params, "float32"),
                          max_slots=3, page_tokens=4, prefill_chunk=16,
                          n_pages=n_pages)


@pytest.mark.parametrize("family", ["gpt", "evabyte"])
def test_per_layer_pool_ledger_decode_cost_flat(family, net, monkeypatch):
    """What ROADMAP S3 promises of the decode program of either family,
    read from its TPU lowering (from shapes, on the CPU) at two pool
    sizes: attention is the paged kernel, called once a layer, no
    tensor of a gathered view's shape — ``(S, P, H, pt, d)`` out of the
    gather, ``(S, H, view rows, d)`` into the einsums — is left in it
    whatever the pool's size, and every per-layer pool leaf is still
    donated. (The CPU's own decode program keeps the XLA expression and
    its view: its scratch is no measure of the TPU's.)"""
    from incubator_mxnet_tpu.ops import _dispatch

    monkeypatch.setattr(_dispatch, "use_pallas", lambda: True)
    monkeypatch.setattr(_dispatch, "interpret_default", lambda: False)
    n_layers = 2
    pools = []
    for n_pages in (40, 160):
        if family == "gpt":
            e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=8,
                                  n_pages=n_pages)
            slots = e._sched.slots
        else:
            e, slots = None, _eva_slots(n_pages)
        try:
            text = _decode_lowering(slots)
            pools.append(slots.cache_bytes)
            leaf_shape = slots._pools["k"][0].shape
        finally:
            if e is not None:
                e.shutdown(drain=False)
        S, P, pt = slots.max_slots, slots.pages_per_slot, slots.page_tokens
        _, H, d, _ = slots._dec.kv_geometry()
        # one kernel, lowered once, called once a layer
        assert "mx_paged_decode" in text and "tpu_custom_call" in text
        assert text.count("call @_pallas_paged_decode") == n_layers
        for view in ((S, P, H, pt, d), (S, H, P * pt, d)):
            assert "x".join(map(str, view)) + "xf32" not in text, view
        # all 2L pool leaves are donated
        leaf = "tensor<" + "x".join(map(str, leaf_shape)) + "xf32>"
        donated = [a for a in _donated_args(text) if leaf in a]
        assert len(donated) == 2 * n_layers, len(donated)
    assert pools[1] >= 3.5 * pools[0]              # the pool really grew


def test_engine_serves_after_the_blocks_arrays_are_deleted():
    """What `chipbench/runners/serve.py` does at GPT-2 XL's size, where two
    float32 copies and the KV pool do not fit one chip: build the engine,
    `.delete()` every ``blocks.*`` array of the Gluon block, serve. Every
    per-layer leaf of the decoder is a buffer of its own (PR 32), so the
    tokens are those of `generate` on the untouched block."""
    m = _spicy_net()                 # its own: the arrays are deleted
    prompts, budgets = _mixed_requests(4, seed=5)
    want = _references(GPTDecoder(m), prompts, budgets)
    e = serve.ServeEngine(m, max_slots=2, max_len=64, max_queue=8)
    try:
        for name, p in m.collect_params().items():
            if name.startswith("blocks."):
                p.data()._data.delete()  # noqa: SLF001
        handles = [e.submit(p, b) for p, b in zip(prompts, budgets)]
        while e.step():
            pass
        assert [h.result() for h in handles] == want
    finally:
        e.shutdown(drain=False)


def test_decode_program_reads_the_weights_as_they_are_stored(net):
    """The decode program lowered for the TPU (from shapes) neither
    transposes nor slices a parameter: every matrix goes into its
    ``dot_general`` as the argument it came in as (a weight sliced out of a
    stack or multiplied transposed is a copy the chip makes, and may
    re-lay out, every step: `PERF.md` §6, PR 32)."""
    import re

    import jax

    e = serve.ServeEngine(net, max_slots=3, max_len=64, max_queue=8)
    try:
        slots = e._sched.slots
        text = _decode_lowering(slots)
        params = slots._dec._params
    finally:
        e.shutdown(drain=False)
    weights = {"tensor<" + "x".join(map(str, a.shape)) + "xf32>"
               for a in jax.tree.leaves(params) if a.ndim >= 2}
    assert len(weights) >= 5          # four matrices a layer, two tables
    main = text[text.index("func.func public @main"):]
    main = main[:main.index("\n  }\n") + 1]
    args = dict(re.findall(r"(%arg\d+): (tensor<[^>]*>)", main))
    ours = {a for a, t in args.items() if t in weights}
    assert len(ours) == 2 * 4 + 2
    uses = []
    for line in main.splitlines():
        m = re.search(r'= "?stablehlo\.(\w+)"?[ (](.*)', line)
        if not m:
            continue
        op, rest = m.groups()
        used = set(re.findall(r"%arg\d+", rest)) & ours
        if not used:
            continue
        # a matrix is multiplied, a table gathered from: nothing else
        assert op in ("dot_general", "gather"), line.strip()[:200]
        uses.append(op)
    # the tied embedding twice: the rows' gather and the logits' product
    assert sorted(uses) == ["dot_general"] * (2 * 4 + 1) + ["gather"] * 2


def test_float_and_int8_engines_expose_one_program_signature(net):
    """The page format is the cache-access objects' alone: each of the
    four programs takes the pools as ONE argument, donated leaf for leaf,
    and reads the same signature whether the pages are float or int8."""
    import inspect

    import jax

    seen = {}
    for kv in ("fp", "int8"):
        e = serve.ServeEngine(net, max_slots=2, max_len=64, max_queue=8,
                              kv_dtype=kv, spec_k=2, draft=net)
        try:
            slots = e._sched.slots
            seen[kv] = [
                str(inspect.signature(build()._fn))
                for build in (slots._build_prefill, slots._build_decode,
                              slots._build_verify, slots._build_draft)]
            leaves = jax.tree.leaves(slots._make_pools(slots._dec))
            assert len(_donated_args(_decode_lowering(slots, "cpu"))) \
                == len(leaves) == (4 if kv == "fp" else 8)
        finally:
            e.shutdown(drain=False)
    assert seen["fp"] == seen["int8"]
    assert all(sig.startswith("(params, pools, ") for sig in seen["fp"])
