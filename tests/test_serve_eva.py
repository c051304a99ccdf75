"""`mx.serve` for the EvaByte family (`serve/eva.py`, `models/evabyte.py`):
the program against the plain reference through `ServeEngine` across rolls
in prefill and in decode, the table `summary pages ++ window pages` through
`paged_decode_attention`, the page arithmetic against a simulation, the
allocator through rolls, endings and cancellations, and the refusals."""
import math

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from chipbench.reference import evabyte as ref
from chipbench.runners import serve_eva
from incubator_mxnet_tpu.models.evabyte import (EvaByteConfig, EvaByteDecoder,
                                                summarize)
from incubator_mxnet_tpu.ops import paged_attention
from incubator_mxnet_tpu.serve import ShardedSlotDecoder, SlotDecoder
from incubator_mxnet_tpu.serve.eva import EvaSlotDecoder
from incubator_mxnet_tpu.telemetry import registry, tracing

# 2 layers x 64, 4 heads of 16, window 32, chunk 4 (8 window pages of 4 rows
# and 2 summary pages a window)
CFG = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
           intermediate_size=96, vocab_size=50, num_pred_heads=3,
           window_size=32, chunk_size=4, rope_theta=100000, rms_norm_eps=1e-5,
           init_std=0.2, max_position_embeddings=160)
ENGINE = dict(max_slots=3, max_len=160, page_tokens=4, prefill_chunk=8)


@pytest.fixture(scope="module")
def dec():
    return serve_eva.build_decoder(CFG, 7, ref, "float32")


def drive(eng, handles, limit=4000):
    for _ in range(limit):
        if all(h.done for h in handles):
            return
        eng.step()
    raise AssertionError("requests did not finish")


# -- (a) the program against the reference ------------------------------------

def test_served_logits_are_the_references_across_rolls(dec):
    """Three requests of unlike lengths in the slots at once (a fourth
    queued behind them): chunked prefill across two rolls, then decode
    across a third; head-0 logits at every served position within 1e-4."""
    registry.reset()
    eng = mx.serve.ServeEngine(dec, **ENGINE)
    rng = onp.random.default_rng(0)
    prompts = [rng.integers(0, 50, n).astype(onp.int32)
               for n in (70, 33, 90, 12)]
    new = [40, 35, 10, 60]      # 70 + 40: rolls at 32, 64 (prefill), 96
    handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
    drive(eng, handles)
    outs = [h.result() for h in handles]
    tokens = onp.zeros((4, 160), onp.int32)
    rows = []
    for b, (p, o) in enumerate(zip(prompts, outs)):
        seq = onp.concatenate([p, onp.asarray(o, onp.int32)])
        tokens[b, :seq.size - 1] = seq[:-1]
        rows += [(b, p.size - 1 + j) for j in range(len(o))]
    logits = ref.logits_at(CFG, 7, tokens, rows)
    served = onp.concatenate([onp.asarray(o) for o in outs])
    gap = logits.max(-1) - logits[onp.arange(served.size), served]
    assert served.size == sum(new) and gap.max() <= 1e-4
    # rolls: (70+40-2)//32 + (33+35-2)//32 + (90+10-2)//32 + (12+60-2)//32
    assert registry.counter("mx_serve_eva_rolls_total").value == 3 + 2 + 3 + 2
    slots = eng._sched.slots
    assert slots.allocator.free_pages == slots.allocator.usable_pages
    rep = {k: v["value"] for k, v in registry.report().items()
           if "value" in v}
    assert rep['mx_serve_decode_rows_total{kind="summary"}'] > 0
    assert rep['mx_serve_decode_rows_total{kind="window"}'] > 0
    assert rep['mx_serve_pages_in_use{kind="window"}'] == 0
    assert rep['mx_kernel_dispatch_total{impl="xla",'
               'op="paged_decode_attention"}'] >= 2
    eng.shutdown(drain=False)


def test_step_records_charge_the_roll_inside_wall(dec):
    tracing.reset()
    eng = mx.serve.ServeEngine(dec, **ENGINE)
    h = eng.submit(onp.arange(40, dtype=onp.int32) % 50, 30)
    drive(eng, [h])
    recs = tracing.step_records()
    rolled = [r for r in recs if r["eva_roll"] > 0]
    assert len(rolled) == 2                      # at 32 (prefill), 64 (decode)
    for r in recs:
        assert sum(r[ph] for ph in tracing.PHASES
                   if ph != "lock_wait") <= r["wall"] + 1e-9
    eng.shutdown(drain=False)


def test_a_roll_with_a_step_in_flight_serves_the_references_tokens(dec):
    """Two slots in decode; one crosses a window boundary while the step
    before it is still unfetched: the roll is queued behind that step, the
    freed window pages are written again only by programs queued later, and
    the served tokens are the plain reference's (teacher-forced, head 0)."""
    tracing.reset()
    eng = mx.serve.ServeEngine(dec, **ENGINE)
    rng = onp.random.default_rng(3)
    prompts = [rng.integers(0, 50, n).astype(onp.int32) for n in (27, 50)]
    new = [30, 30]              # rolls in decode at 32 and at 64
    handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
    drive(eng, handles)
    outs = [h.result() for h in handles]
    rolled_ahead = [r for r in tracing.step_records()
                    if r["eva_roll"] > 0 and r["mode"] == "ahead"
                    and not r["chunks"]]
    assert len(rolled_ahead) == 2           # both decode rolls, a step ahead
    tokens = onp.zeros((2, 160), onp.int32)
    rows = []
    for b, (p, o) in enumerate(zip(prompts, outs)):
        seq = onp.concatenate([p, onp.asarray(o, onp.int32)])
        tokens[b, :seq.size - 1] = seq[:-1]
        rows += [(b, p.size - 1 + j) for j in range(len(o))]
    logits = ref.logits_at(CFG, 7, tokens, rows)
    served = onp.concatenate([onp.asarray(o) for o in outs])
    gap = logits.max(-1) - logits[onp.arange(served.size), served]
    assert served.size == 60 and gap.max() <= 1e-4
    slots = eng._sched.slots
    assert slots.allocator.free_pages == slots.allocator.usable_pages
    assert eng._sched.idle
    eng.shutdown(drain=False)


def test_an_eos_after_a_roll_gives_every_page_back(dec):
    """The overshoot row of an EOS learnt a step late may have mapped a page
    or rolled a window for a request that was already over: retirement
    gives back whatever the slot holds."""
    eng = mx.serve.ServeEngine(dec, **ENGINE)
    p = onp.random.default_rng(5).integers(0, 50, 29).astype(onp.int32)
    h = eng.submit(p, 12)
    drive(eng, [h])
    free = h.result()
    # the token served at position 31: the row after it opens a window
    eos = free[2]
    assert eos not in free[:2]
    from incubator_mxnet_tpu.serve.scheduler import OVERSHOOT_ROWS

    before = OVERSHOOT_ROWS.value
    h2 = eng.submit(p, 12, eos_id=int(eos))
    drive(eng, [h2])
    assert h2.result() == free[:3]
    assert eng.step() is True       # fetches the step launched ahead
    assert OVERSHOOT_ROWS.value == before + 1
    slots = eng._sched.slots
    assert slots.allocator.free_pages == slots.allocator.usable_pages
    eng.shutdown(drain=False)


def test_summaries_are_the_references():
    rng = onp.random.default_rng(1)
    k, v = (rng.normal(size=(24, 3, 8)).astype(onp.float32) for _ in range(2))
    phi, mu = (rng.normal(size=(3, 8)).astype(onp.float32) for _ in range(2))
    want_k, want_v = ref.summaries(jnp.asarray(k), jnp.asarray(v), phi, mu, 4)
    kc = jnp.transpose(jnp.asarray(k).reshape(6, 4, 3, 8), (2, 0, 1, 3))
    vc = jnp.transpose(jnp.asarray(v).reshape(6, 4, 3, 8), (2, 0, 1, 3))
    got_k, got_v = summarize(kc, vc, phi[:, None, :], mu[:, None, :])
    onp.testing.assert_allclose(jnp.transpose(got_k, (1, 0, 2)), want_k,
                                rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(jnp.transpose(got_v, (1, 0, 2)), want_v,
                                rtol=1e-5, atol=1e-6)


# -- (c) summary ++ window through the paged op -------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("d,dtype", [(128, "float32"), (16, "float32"),
                                     (128, "bfloat16")])
def test_summary_then_window_pages_through_paged_decode(impl, d, dtype):
    """A slot's row: its summary pages, then its window pages; `lengths` the
    row count. Against dense softmax over the concatenated rows. bfloat16 at
    head size 128 is the cell's own form: one query row a head through the
    kernel's MXU products."""
    rng = onp.random.default_rng(d)
    S, H, pt, P, n_pages = 3, 2, 16, 8, 40
    pool_k, pool_v, q = (
        onp.asarray(jnp.asarray(rng.normal(size=shape), dtype), onp.float32)
        for shape in ((n_pages, H, pt, d), (n_pages, H, pt, d), (S, H, d)))
    table = onp.zeros((S, P), onp.int32)
    lengths = onp.zeros(S, onp.int32)
    want = onp.zeros((S, H, d), onp.float32)
    free = list(rng.permutation(onp.arange(1, n_pages)))
    for s, (n_sum, in_window) in enumerate([(2, 21), (0, 5), (3, 64)]):
        n_win = -(-in_window // pt)
        pages = [free.pop() for _ in range(n_sum + n_win)]
        table[s, :len(pages)] = pages
        lengths[s] = n_sum * pt + in_window
        k = onp.concatenate([pool_k[p] for p in pages], 1)[:, :lengths[s]]
        v = onp.concatenate([pool_v[p] for p in pages], 1)[:, :lengths[s]]
        e = onp.einsum("hd,hnd->hn", q[s], k) / math.sqrt(d)
        e = onp.exp(e - e.max(-1, keepdims=True))
        want[s] = onp.einsum("hn,hnd->hd", e / e.sum(-1, keepdims=True), v)
    pk, pv = (paged_attention.pack_pages(jnp.asarray(a, dtype))
              for a in (pool_k, pool_v))
    args = (jnp.asarray(q, dtype), pk, pv, jnp.asarray(table),
            jnp.asarray(lengths))
    assert paged_attention._on_mxu(args[0], pk) is (dtype == "bfloat16")
    if impl == "pallas":
        got = paged_attention._pallas_paged_decode(*args, True)
    else:
        got = paged_attention._xla_paged_decode(*args, None, None)
    tol = 8e-3 if dtype == "bfloat16" else 2e-5
    onp.testing.assert_allclose(onp.asarray(got, onp.float32), want,
                                rtol=tol, atol=tol)


# -- (d) the page arithmetic and the allocator --------------------------------

def slots_of(**kw):
    """A slots object of the published page geometry (no weights needed for
    its arithmetic): window 2048, chunk 16, pages of 16."""
    cfg = EvaByteConfig(num_hidden_layers=1, hidden_size=32,
                        num_attention_heads=2, intermediate_size=32,
                        vocab_size=8, num_pred_heads=1)
    top, layer = cfg.leaf_shapes()
    params = {n: jnp.zeros(s) for n, s in top.items()}
    params["layers"] = [{n: jnp.zeros(s) for n, s in layer.items()}]
    return EvaSlotDecoder(EvaByteDecoder(cfg, params, "float32"),
                          page_tokens=16, prefill_chunk=512, **kw)


@pytest.mark.parametrize("n", [1, 15, 16, 17, 2047, 2048, 2049, 4096, 4097,
                               17664, 30720, 32768])
def test_pages_needed_is_the_most_a_simulated_request_holds(n):
    """``summary_pages * ((n - 1) // window) + min(window_pages, ceil(n /
    page_tokens))``: walk a request of `n` positions, position by position
    at the boundaries, holding what the engine holds."""
    slots = slots_of(max_slots=1)
    assert (slots.window_pages, slots.summary_pages,
            slots.pages_per_slot) == (128, 8, 248)
    held = peak = 0
    for pos in range(n):
        if pos % 16 and pos != n - 1:
            continue                     # pages change at page starts only
        if slots.rolls_before(pos):
            peak = max(peak, held + 8)   # the new summaries, then the old go
            held += 8 - 128
        held = max(held, slots.pages_at(pos + 1))
        peak = max(peak, held)
    assert held == slots.pages_at(n)
    assert peak == slots.pages_needed(n) \
        == 8 * ((n - 1) // 2048) + min(128, -(-n // 16))


def test_a_roll_frees_a_window_and_takes_its_summaries():
    """Published geometry through the scheduler: 128 pages back, 8 taken; and
    the free pages return to the start after endings and a cancellation in
    the middle of a window."""
    slots = slots_of(max_slots=2, max_len=8192, n_pages=600)
    sched = mx.serve.Scheduler(slots)
    alloc = slots.allocator
    rng = onp.random.default_rng(2)
    a = sched.submit(rng.integers(0, 8, 2040).astype(onp.int32), 40)
    b = sched.submit(rng.integers(0, 8, 600).astype(onp.int32), 2000)
    assert sched._pages_needed(a) == 8 + 128
    used = []
    while not a.done:
        sched.step()
        used.append((int(sched._pos[a.slot]) if a.slot is not None else -1,
                     len(a.pages or ())))
    before = max(n for pos, n in used if 0 < pos <= 2048)
    after = min(n for pos, n in used if pos > 2048)
    # -128 + 8 at the roll, then the 31 positions the request has left: 2
    assert (before, after) == (128, 8 + 2)
    assert registry.report()[
        'mx_serve_pages_in_use{kind="summary"}']["value"] == 0
    for _ in range(300):
        sched.step()
    assert b.pages and alloc.used_pages == len(b.pages)
    sched.preempt(b.slot)                        # cancelled mid-window
    assert alloc.free_pages == alloc.usable_pages
    assert sched._spec_reserved_total() == 0


def test_reservations_keep_admission_from_overcommitting(dec):
    """Pages not yet taken count against later admissions: with a pool that
    holds one request's worst case and a half, the second waits."""
    slots = EvaSlotDecoder(dec, max_slots=2, max_len=160, page_tokens=4,
                           prefill_chunk=8, n_pages=1 + 18)
    sched = mx.serve.Scheduler(slots)
    p = onp.arange(60, dtype=onp.int32) % 50
    a, b = sched.submit(p, 30), sched.submit(p, 30)
    assert sched._pages_needed(a) == 2 * 2 + 8
    sched.step()
    assert a.state == "running" and b.state == "queued"
    while not a.done:
        sched.step()
        assert slots.allocator.free_pages >= sched._spec_reserved_total()
    while not b.done:
        sched.step()
    assert slots.allocator.free_pages == slots.allocator.usable_pages


# -- (e) the refusals ---------------------------------------------------------

@pytest.mark.parametrize("kw", [{"spec_k": 2}, {"kv_dtype": "int8"},
                                {"prefix_reuse": True}, {"draft": "ngram"}],
                         ids=lambda kw: next(iter(kw)))
def test_what_the_family_is_not_served_with_raises(dec, kw):
    with pytest.raises(NotImplementedError, match="evabyte"):
        mx.serve.ServeEngine(dec, **ENGINE, **kw)


@pytest.mark.parametrize("family", [SlotDecoder, ShardedSlotDecoder])
def test_other_engines_refuse_the_family(dec, family):
    """They name what they serve and what they were handed, not whom else
    to ask."""
    with pytest.raises(TypeError, match="GPTDecoder.*got EvaByteDecoder"):
        family(dec, max_slots=2)


def test_prefill_only_handoff_is_refused(dec):
    eng = mx.serve.ServeEngine(dec, **ENGINE)
    with pytest.raises(NotImplementedError):
        eng._sched.submit(onp.arange(9, dtype=onp.int32), 4,
                          prefill_only=True)
    with pytest.raises(NotImplementedError):
        eng._sched.adopt_page_plan(9, 4)


def test_bfloat16_pages_are_counted_as_bfloat16():
    """`page_bytes`, `cache_bytes` and `kv_bytes_per_slot` of a bfloat16
    engine: K + V, 2 bytes a value, both page kinds in the one pool."""
    cfg = EvaByteConfig.from_dict(CFG)
    top, layer = cfg.leaf_shapes()
    params = {n: jnp.zeros(s) for n, s in top.items()}
    params["layers"] = [{n: jnp.zeros(s) for n, s in layer.items()}
                        for _ in range(2)]
    slots = EvaSlotDecoder(EvaByteDecoder(cfg, params, "bfloat16"),
                           max_slots=2, max_len=160, page_tokens=4,
                           prefill_chunk=8)
    assert slots.page_bytes == 2 * (2 * 4 * 4 * 16 * 2)
    slots._ensure_pool()
    assert slots._pools["k"][0].dtype == jnp.bfloat16
    assert slots.cache_bytes == slots.n_pages * slots.page_bytes
    assert slots.kv_bytes_per_slot == slots.cache_bytes / 2
