"""The decode step in flight (ISSUE 30): `Scheduler` launches step k+1 before
it fetches step k's tokens, for every slots family. Served tokens are those
of the serial order of work (launch, fetch, hand out, then the next launch),
greedy and sampled; what the host knows in advance costs nothing (an ending
by length: no row, no key); what it learns a step late costs one dropped row
(an EOS); whatever changes a slot between steps settles the step in flight
first. All on the CPU: tokens, counts and orderings, never a speed."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from chipbench.reference import evabyte as eva_ref
from chipbench.runners import serve_eva
from incubator_mxnet_tpu import np
from incubator_mxnet_tpu.models.gpt import gpt_tiny
from incubator_mxnet_tpu.serve import scheduler as sched_mod
from incubator_mxnet_tpu.serve.engine import SlotDecoder
from incubator_mxnet_tpu.serve.eva import EvaSlotDecoder
from incubator_mxnet_tpu.serve.scheduler import (DeadlineExceeded,
                                                 EngineClosed, Scheduler)
from incubator_mxnet_tpu.serve.sharded import ShardedSlotDecoder, serve_mesh
from incubator_mxnet_tpu.telemetry import tracing

VOCAB = 97
EVA_CFG = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               intermediate_size=96, vocab_size=50, num_pred_heads=3,
               window_size=32, chunk_size=4, rope_theta=100000,
               rms_norm_eps=1e-5, init_std=0.2, max_position_embeddings=160)
FAMILIES = ("gpt", "gpt_int8", "eva", "sharded")


@pytest.fixture(scope="module")
def net():
    mx.random.seed(11)
    m = gpt_tiny(vocab_size=VOCAB, max_length=64, dropout=0.0)
    m.initialize()
    r = onp.random.RandomState(42)
    for _name, p in m.collect_params().items():
        if p.shape and len(p.shape) >= 2:
            p.set_data(np.array(r.normal(0, 0.35, p.shape).astype("float32")))
    return m


@pytest.fixture(scope="module")
def eva_dec():
    return serve_eva.build_decoder(EVA_CFG, 7, eva_ref, "float32")


@pytest.fixture
def make(net, eva_dec):
    """``make(family, **engine settings)`` -> a slots object of that family."""
    def build(family, **kw):
        gpt = dict(max_slots=3, max_len=64, prefill_chunk=16, page_tokens=8)
        if family == "gpt":
            return SlotDecoder(net, **{**gpt, **kw})
        if family == "gpt_int8":
            return SlotDecoder(net, kv_dtype="int8", **{**gpt, **kw})
        if family == "sharded":
            import jax

            mesh = serve_mesh({"tp": 2}, devices=jax.devices()[:2])
            return ShardedSlotDecoder(net, mesh=mesh, **{**gpt, **kw})
        return EvaSlotDecoder(eva_dec, **{**dict(
            max_slots=3, max_len=160, page_tokens=4, prefill_chunk=8), **kw})
    return build


def vocab(family):
    return EVA_CFG["vocab_size"] if family == "eva" else VOCAB


def prompt(family, n, seed):
    return onp.random.RandomState(seed).randint(
        0, vocab(family), (n,)).astype(onp.int32)


def serial(slots):
    """The parent's order of work on the same programs: a `decode_step`
    that hands back host tokens leaves nothing in flight, so the scheduler
    hands them out before it launches again."""
    inner = slots.decode_step
    slots.decode_step = lambda *a: onp.asarray(inner(*a))
    return slots


def drive(sched, reqs, limit=4000):
    for _ in range(limit):
        if all(r.done for r in reqs):
            return
        sched.step()
    raise AssertionError("requests did not finish")


# prompts long and short, outputs of 1 and 2 among them, more requests than
# slots: requests join a running batch and leave it mid-stream, and the
# queue refills a slot the step after it is freed
LENS = (5, 40, 23, 9, 31, 17, 12, 3)
NEWS = (7, 3, 12, 1, 5, 9, 2, 6)


def serve_waves(sched, family, temperature):
    reqs = []
    for i, (n, k) in enumerate(zip(LENS, NEWS)):
        reqs.append(sched.submit(prompt(family, n, i), k,
                                 temperature=temperature))
        if i % 3 == 2:
            for _ in range(4):
                sched.step()
    drive(sched, reqs)
    return [r.result() for r in reqs]


# -- the same tokens as the serial order of work ------------------------------

@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", FAMILIES)
def test_tokens_are_those_of_the_serial_order(make, family, sampled):
    kw = dict(do_sample=True, top_k=8) if sampled else {}
    temperature = 0.8 if sampled else 1.0
    launches0 = {m: c.value for m, c in sched_mod.DECODE_LAUNCHES.items()}
    ahead = Scheduler(make(family, **kw), max_queue=16, seed=5)
    got = serve_waves(ahead, family, temperature)
    launched = {m: c.value - launches0[m]
                for m, c in sched_mod.DECODE_LAUNCHES.items()}
    base = Scheduler(serial(make(family, **kw)), max_queue=16, seed=5)
    want = serve_waves(base, family, temperature)
    assert got == want
    assert [len(t) for t in got] == list(NEWS)
    # the same programs in the same order took the same keys
    assert ahead._key_ctr == base._key_ctr
    # most launches were queued behind an unfetched step; the serial order
    # never has one
    assert launched["ahead"] > 2 * launched["cold"] > 0
    alloc = ahead.slots.allocator
    ahead.slots.prefix_cache.clear()
    assert alloc.used_pages == 0 and ahead.idle


# -- what the host knows in advance -------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_an_ending_by_length_costs_no_row_and_no_key(make, family):
    """One request of 6 tokens: the prompt's chunk and 5 decode launches,
    a key each, and its slot is free as soon as the fifth is queued."""
    over0 = sched_mod.OVERSHOOT_ROWS.value
    sched = Scheduler(make(family), max_queue=4, seed=0)
    rows = []
    inner = sched.slots.decode_step

    def spy(last, pos, active, key, temps):
        rows.append(int(onp.sum(active)))
        return inner(last, pos, active, key, temps)

    sched.slots.decode_step = spy
    req = sched.submit(prompt(family, 7, 0), 6)
    freed_at = None
    for i in range(50):
        if req.done:
            break
        sched.step()
        if freed_at is None and req.slot is None and req.state == "running":
            freed_at = len(req.tokens)
    assert req.done and len(req.tokens) == 6
    assert rows == [1] * 5 and sched._key_ctr == 1 + 5
    assert sched_mod.OVERSHOOT_ROWS.value == over0
    # freed with its last token (and the one before) still on the way
    assert freed_at is not None and freed_at < 6
    assert sched.n_active == 0 and sched.idle


@pytest.mark.parametrize("family", FAMILIES)
def test_an_eos_is_learnt_a_step_late_and_its_row_dropped(make, family):
    """The EOS of one request, while another goes on: nothing is handed
    out after it, the row launched for it in the next step is counted and
    dropped, the other request's tokens are what they are without it, and
    every page comes back."""
    free = Scheduler(make(family), max_queue=4, seed=0)
    prompts = [prompt(family, 9, 1), prompt(family, 13, 2)]
    free_run = [free.submit(p, 10) for p in prompts]
    drive(free, free_run)
    # an EOS that first shows at a request's 3rd to 8th token: that request
    # is `a`, the other goes on
    first = [[i for i in range(2, 8) if r.tokens[i] not in r.tokens[:i]]
             for r in free_run]
    ia = 0 if first[0] else 1
    at = first[ia][0]
    (a, toks_a), (b, toks_b) = [(prompts[i], free_run[i].result())
                                for i in (ia, 1 - ia)]
    over0 = sched_mod.OVERSHOOT_ROWS.value
    tracing.reset()
    sched = Scheduler(make(family), max_queue=4, seed=0)
    ra = sched.submit(a, 10, eos_id=int(toks_a[at]))
    rb = sched.submit(b, 10)
    drive(sched, [ra, rb])
    assert ra.result() == toks_a[:at + 1]
    assert rb.result() == toks_b
    assert sched_mod.OVERSHOOT_ROWS.value == over0 + 1
    assert sum(r["overshoot"] for r in tracing.step_records()) == 1
    sched.slots.prefix_cache.clear()
    assert sched.slots.allocator.used_pages == 0 and sched.idle


# -- whatever changes a slot settles the step in flight first ------------------

def in_flight(sched, reqs, tokens=2):
    """Step until a decode step is in flight and every request has been
    handed `tokens` tokens or more."""
    for _ in range(200):
        sched.step()
        if sched._flight is not None \
                and all(len(r.tokens) >= tokens for r in reqs):
            return
    raise AssertionError("no step in flight")


@pytest.fixture
def reference(make):
    """Tokens of two requests served alone, per family."""
    def tokens(family, new=10):
        sched = Scheduler(make(family), max_queue=4, seed=0)
        reqs = [sched.submit(prompt(family, 9, 1), new),
                sched.submit(prompt(family, 13, 2), new)]
        drive(sched, reqs)
        return [r.result() for r in reqs]
    return tokens


@pytest.mark.parametrize("family", FAMILIES)
def test_a_deadline_with_a_step_in_flight_keeps_its_tokens(
        make, reference, family):
    want = reference(family)
    sched = Scheduler(make(family), max_queue=4, seed=0)
    ra = sched.submit(prompt(family, 9, 1), 10, deadline_s=1e4)
    rb = sched.submit(prompt(family, 13, 2), 10)
    in_flight(sched, [ra, rb])
    n = len(ra.tokens)
    ra.deadline = 0.0                       # expired, a step in flight
    sched.step()
    assert ra.done and isinstance(ra.error, DeadlineExceeded)
    # the step that was in flight when it expired was the request's
    assert ra.tokens == want[0][:n + 1]
    drive(sched, [rb])
    assert rb.result() == want[1]
    sched.slots.prefix_cache.clear()
    assert sched.slots.allocator.used_pages == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_preempt_with_a_step_in_flight_loses_and_repeats_nothing(
        make, reference, family):
    """The preempted request's tokens so far, then the resumed remainder:
    together what it would have been served without the preemption."""
    want = reference(family)
    sched = Scheduler(make(family), max_queue=4, seed=0)
    ra = sched.submit(prompt(family, 9, 1), 10)
    rb = sched.submit(prompt(family, 13, 2), 10)
    in_flight(sched, [ra, rb], tokens=3)
    n = len(ra.tokens)
    out = sched.preempt(ra.slot)
    assert out is ra and ra.state == "preempted" and sched._flight is None
    assert ra.tokens == want[0][:n + 1]     # the step in flight came first
    rest = sched.submit(
        onp.concatenate([ra.prompt, onp.asarray(ra.tokens, onp.int32)]),
        10 - len(ra.tokens))
    drive(sched, [rb, rest])
    assert ra.tokens + rest.result() == want[0]
    assert rb.result() == want[1]


def test_preempt_of_a_request_whose_last_token_is_in_flight(make):
    """Its slot was freed when its last step was launched: the settle hands
    it its last token, and there is nothing left to preempt."""
    sched = Scheduler(make("gpt"), max_queue=4, seed=0)
    ra = sched.submit(prompt("gpt", 9, 1), 3)
    rb = sched.submit(prompt("gpt", 13, 2), 9)
    sched.step()
    slot = ra.slot
    for _ in range(20):
        if ra.slot is None:
            break
        sched.step()
    assert ra.slot is None and not ra.done and sched._in_slot[slot] is None
    with pytest.raises(ValueError, match="empty"):
        sched.preempt(slot)
    assert ra.done and len(ra.result()) == 3 and not rb.done


@pytest.mark.parametrize("drain", [True, False])
@pytest.mark.parametrize("family", FAMILIES)
def test_close_with_a_step_in_flight_delivers_it(make, reference, family,
                                                 drain):
    want = reference(family)
    sched = Scheduler(make(family), max_queue=4, seed=0)
    reqs = [sched.submit(prompt(family, 9, 1), 10),
            sched.submit(prompt(family, 13, 2), 10)]
    in_flight(sched, reqs)
    n = [len(r.tokens) for r in reqs]
    sched.close(drain=drain)
    assert sched._flight is None
    if drain:
        while sched.n_active:
            sched.step()
        assert [r.result() for r in reqs] == want
    else:
        for r, k, w in zip(reqs, n, want):
            assert isinstance(r.error, EngineClosed)
            assert r.tokens == w[:k + 1]
    sched.slots.prefix_cache.clear()
    assert sched.slots.allocator.used_pages == 0


def test_abandon_drops_the_step_in_flight_unfetched(make, reference):
    """A dead engine's tokens in flight were handed to nobody: the work
    re-queued elsewhere makes them again, once."""
    want = reference("gpt")
    sched = Scheduler(make("gpt"), max_queue=4, seed=0)
    reqs = [sched.submit(prompt("gpt", 9, 1), 10),
            sched.submit(prompt("gpt", 13, 2), 10)]
    in_flight(sched, reqs)
    kept = [list(r.tokens) for r in reqs]
    sched.abandon()
    assert sched._flight is None and sched.idle
    assert [list(r.tokens) for r in reqs] == kept
    other = Scheduler(make("gpt"), max_queue=4, seed=0)
    rest = [other.submit(
        onp.concatenate([r.prompt, onp.asarray(r.tokens, onp.int32)]),
        10 - len(r.tokens)) for r in reqs]
    drive(other, rest)
    assert [k + r.result() for k, r in zip(kept, rest)] == want


@pytest.mark.parametrize("family", ["gpt", "gpt_int8", "sharded"])
def test_adopt_beside_a_step_in_flight(make, family):
    """A prefill-only segment handed off and adopted into the engine whose
    own request has a step in flight: both are served what a co-located
    engine serves them."""
    solo = Scheduler(make(family), max_queue=4, seed=0)
    refs = [solo.submit(prompt(family, 9, 1), 10),
            solo.submit(prompt(family, 16, 2), 8)]
    drive(solo, refs)
    want = [r.result() for r in refs]

    sched = Scheduler(make(family), max_queue=4, seed=0)
    ra = sched.submit(prompt(family, 9, 1), 10)
    seg = sched.submit(prompt(family, 16, 2), 8, prefill_only=True)
    in_flight(sched, [ra])
    (handed,) = sched.take_prefilled()
    assert handed is seg and seg.state == "prefilled"
    assert sched._flight is not None        # a handoff settles nothing
    content, physical, reserved = sched.adopt_page_plan(16, 8)
    pages = list(seg.pages) + sched.slots.allocator.alloc(
        physical - len(seg.pages))
    sched.finish_handoff(seg)
    rb = sched.adopt(seg.prompt, seg.first_token, 8, pages,
                     spec_reserved=reserved)
    drive(sched, [ra, rb])
    assert ra.result() == want[0]
    assert rb.result() == want[1]


# -- idle, and a loop that steps until idle ------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_not_idle_while_a_step_is_in_flight(make, family):
    sched = Scheduler(make(family), max_queue=4, seed=0)
    req = sched.submit(prompt(family, 7, 0), 4)
    seen = False
    steps = 0
    while not sched.idle:
        assert sched.step() is True         # every step to idle progresses
        steps += 1
        if sched._flight is not None and sched._n_active == 0:
            # no slot is occupied, and the last tokens are still owed
            seen = True
            assert not sched.idle and sched.n_active == 1
        assert steps < 100
    assert seen and req.done and len(req.result()) == 4
    assert sched.step() is False


# -- step records ----------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_step_records_say_how_each_launch_was_made(make, family):
    tracing.reset()
    fetches0 = {k: c.value for k, c in sched_mod.PREFILL_FETCHES.items()}
    sched = Scheduler(make(family), max_queue=4, seed=0)
    long = 40 if family != "eva" else 20    # three chunks
    reqs = [sched.submit(prompt(family, long, 0), 6),
            sched.submit(prompt(family, 5, 1), 8)]
    drive(sched, reqs)
    recs = tracing.step_records()
    assert {r["mode"] for r in recs} == {None, "cold", "ahead"}
    for r in recs:
        assert (r["mode"] is not None) == (r["decoding"] > 0) \
            == (r["decode_launch"] > 0.0)
    # the first launch has nothing before it; a step that only fetches
    # launches nothing
    launches = [r["mode"] for r in recs if r["mode"]]
    assert launches[0] == "cold" and launches.count("ahead") >= 5
    assert recs[-1]["mode"] is None and recs[-1]["decode_readback"] > 0.0
    # an intermediate chunk is launched and never waited for
    chunks = sum(r["chunks"] for r in recs)
    final = sched_mod.PREFILL_FETCHES["final"].value - fetches0["final"]
    skipped = sched_mod.PREFILL_FETCHES["skipped"].value - fetches0["skipped"]
    assert (final, skipped) == (2, chunks - 2) and skipped >= 2
    alone = [r for r in recs if r["chunks"] and not r["prefill_readback"]]
    assert alone and all(r["prefill_launch"] > 0.0 for r in alone)
    assert sum(1 for r in recs if r["prefill_readback"] > 0.0) <= 2
    # the phases cover the steps' wall
    phases = [ph for ph in tracing.PHASES if ph != "lock_wait"]
    covered = sum(r[ph] for r in recs for ph in phases)
    assert covered >= 0.99 * sum(r["wall"] for r in recs)
