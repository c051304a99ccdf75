"""mx.serve's step and request timeline (ISSUE 25): the always-on records
that `telemetry.tracing` keeps of every scheduler iteration and every retired
request, the `mx.serve.*` spans in a live profiler session's own trace, and
the operator's counters fed from the same stamps. All on the CPU: counts and
orderings, never a speed."""
import gc
import glob
import threading
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import np, serve
from incubator_mxnet_tpu.models.decoding import GPTDecoder
from incubator_mxnet_tpu.models.gpt import gpt_tiny
from incubator_mxnet_tpu.serve import scheduler as sched_mod
from incubator_mxnet_tpu.telemetry import anatomy, capacity, registry, tracing

VOCAB = 97
PHASES = [ph for ph in tracing.PHASES if ph != "lock_wait"]


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset()
    yield
    tracing.disable()
    anatomy.disable()
    capacity.disable()
    tracing.reset()


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


def _prompt(n, seed=0):
    return onp.random.RandomState(seed).randint(
        0, VOCAB, (n,)).astype(onp.int32)


PROMPTS = [_prompt(n, seed=i) for i, n in enumerate((5, 40, 23, 9, 31, 17))]


def _engine(net, **kw):
    return serve.ServeEngine(net, max_slots=3, max_len=64, prefill_chunk=16,
                             page_tokens=8, max_queue=32, **kw)


def _drive(eng, prompts, new=5):
    """Submit everything, step by hand; returns the handles, how many steps
    made progress, and the active-slot count of every decode launch."""
    launches = []
    slots = eng._sched.slots
    inner = slots.decode_step

    def spy(last, pos, active, key, temps):
        launches.append(int(onp.sum(active)))
        return inner(last, pos, active, key, temps)

    slots.decode_step = spy
    try:
        handles = [eng.submit(p, new) for p in prompts]
        progressed = 0
        while not all(h.done for h in handles):
            progressed += bool(eng.step())
    finally:
        del slots.decode_step
    return handles, progressed, launches


@pytest.fixture
def served(net):
    """One engine's worth of traffic, stepped by hand, shut down and deleted
    before anything reads the records."""
    eng = _engine(net)
    chunks0 = sched_mod.PREFILL_CHUNKS.value
    t0 = time.perf_counter()
    handles, progressed, launches = _drive(eng, PROMPTS)
    t1 = time.perf_counter()
    out = {"tokens": [list(h.tokens) for h in handles],
           "ttft": [h.ttft for h in handles], "ids": [h.id for h in handles],
           "progressed": progressed, "launches": launches,
           "chunks": sched_mod.PREFILL_CHUNKS.value - chunks0,
           "window": (t0, t1)}
    eng.shutdown(drain=False)
    del eng, handles
    gc.collect()
    return out


# -- step records ------------------------------------------------------------

def test_every_progressed_step_leaves_one_record(served):
    recs = tracing.step_records()
    assert len(recs) == served["progressed"] > 0
    assert [r["t_start"] for r in recs] == sorted(r["t_start"] for r in recs)
    assert set(recs[0]) == {"t_start", "wall", "chunks", "decoding",
                            "prefilling", "queued", "pages_live",
                            "pages_view", "mode", "overshoot",
                            *tracing.PHASES, *tracing.LAUNCH_PARTS, "dry",
                            *("dry_" + c for c in tracing.DRY_CAUSES)}


def test_phases_are_non_negative_and_add_up_to_at_most_wall(served):
    for r in tracing.step_records():
        assert all(r[ph] >= 0.0 for ph in tracing.PHASES), r
        assert sum(r[ph] for ph in PHASES) <= r["wall"] + 1e-9, r
        # the engine's own boundaries are all there: what the phases leave
        # of a step is the two assignments after the last of them
        assert sum(r[ph] for ph in PHASES) >= 0.9 * r["wall"], r


def test_chunks_add_up_to_the_prefill_counter(served):
    assert sum(r["chunks"] for r in tracing.step_records()) \
        == served["chunks"] > len(PROMPTS)


def test_decoding_is_the_active_slots_of_each_decode_launch(served):
    launched = [r["decoding"] for r in tracing.step_records() if r["decoding"]]
    assert launched == served["launches"] and max(launched) == 3
    for r in tracing.step_records():
        assert (r["decode_launch"] > 0.0) == (r["decoding"] > 0)
        assert (r["prefill_launch"] > 0.0) == (r["chunks"] > 0)


def test_mode_says_how_each_decode_launch_was_made(served):
    """`mode`: "ahead" where the step before was still unfetched when this
    one was queued, "cold" where nothing was in flight, None where the
    iteration launched no decode step (it may still have fetched one)."""
    recs = tracing.step_records()
    for r in recs:
        assert (r["mode"] in ("ahead", "cold")) == (r["decoding"] > 0), r
        assert r["overshoot"] == 0          # every request ended by length
    modes = [r["mode"] for r in recs if r["mode"]]
    assert modes[0] == "cold" and modes.count("ahead") > modes.count("cold")
    # the tokens of the last launch are fetched by an iteration of their own
    assert recs[-1]["mode"] is None and recs[-1]["decode_readback"] > 0.0


def test_an_intermediate_chunk_records_no_prefill_readback(net):
    """A 40-token prompt alone: three chunks in three iterations, and only
    the last of them is waited for (its token is the request's first)."""
    eng = _engine(net)
    try:
        h = eng.submit(PROMPTS[1], 2)
        while not h.done:
            eng.step()
    finally:
        eng.shutdown(drain=False)
    chunked = [r for r in tracing.step_records() if r["chunks"]]
    assert [r["chunks"] for r in chunked] == [1, 1, 1]
    assert [r["prefill_readback"] > 0.0 for r in chunked] \
        == [False, False, True]
    assert all(r["prefill_launch"] > 0.0 for r in chunked)


def test_phases_cover_the_steps_wall_with_a_step_in_flight(served):
    """Launch and fetch now lie in different iterations; together with the
    other phases they still account for the loop's time."""
    recs = tracing.step_records()
    covered = sum(r[ph] for r in recs for ph in PHASES)
    assert covered >= 0.99 * sum(r["wall"] for r in recs)


def test_records_window_by_start_and_are_copies(served):
    t0, t1 = served["window"]
    recs = tracing.step_records()
    assert tracing.step_records(t0, t1) == recs
    mid = recs[len(recs) // 2]["t_start"]
    early, late = tracing.step_records(None, mid), tracing.step_records(mid)
    assert len(early) + len(late) == len(recs) and late[0]["t_start"] == mid
    recs[0]["wall"] = -1.0
    assert tracing.step_records()[0]["wall"] >= 0.0
    assert tracing.step_records(t1) == [] == tracing.request_records(t1)


def test_records_outlive_shutdown_and_deletion_and_reset_clears(served):
    # `served` shut the engine down and deleted it before this test ran
    assert tracing.step_records() and tracing.request_records()
    tracing.reset()
    assert tracing.step_records() == [] == tracing.request_records()


def test_rings_are_bounded_and_drop_the_oldest():
    for i in range(tracing.REQUEST_RING_CAPACITY + 3):
        tracing.add_request_record(id=i, t_submit_call=float(i))
    recs = tracing.request_records()
    assert len(recs) == tracing.REQUEST_RING_CAPACITY
    assert recs[0]["id"] == 3 and recs[-1]["id"] == i
    assert tracing.STEP_RING_CAPACITY >= 10 * 60 * 7   # ten minutes at 7/s


def test_a_step_that_raises_leaves_no_record_and_no_open_clock(net):
    from incubator_mxnet_tpu import fault

    eng = _engine(net)
    try:
        eng.submit(PROMPTS[0], 2)
        fault.configure_injection("serve_step:1.0:0:1")
        try:
            with pytest.raises(fault.FaultInjected):
                eng.step()
        finally:
            fault.clear_injection()
        assert tracing.step_records() == []
        assert getattr(tracing._TLS, "clock", None) is None
        assert eng.step() is True and len(tracing.step_records()) == 1
    finally:
        eng.shutdown(drain=False)


# -- request records -----------------------------------------------------------

def test_request_stamps_are_ordered_and_agree_with_ttft(served):
    recs = {r["id"]: r for r in tracing.request_records()}
    assert sorted(recs) == sorted(served["ids"])
    for rid, ttft, toks in zip(served["ids"], served["ttft"],
                               served["tokens"]):
        r = recs[rid]
        assert r["t_submit_call"] <= r["t_enqueued"] <= r["t_admit"] \
            <= r["t_first_token"] <= r["t_finish"], r
        assert abs((r["t_first_token"] - r["t_enqueued"]) - ttft) < 1e-3
        assert r["tokens"] == len(toks) and r["state"] == "done"
        assert r["trace_id"] is None and r["chunks"] >= 1
    assert any(r["chunks"] == 3 for r in recs.values())    # the 40-token prompt


def test_request_record_of_a_failed_request_says_so(net):
    eng = _engine(net)
    try:
        h = eng.submit(PROMPTS[1], 4, deadline_s=0.0)
        time.sleep(0.005)
        eng.step()
        assert h.state == "failed"
    finally:
        eng.shutdown(drain=False)
    (r,) = tracing.request_records()
    assert r["state"] == "failed" and r["t_admit"] is None
    assert r["t_first_token"] is None and r["t_finish"] >= r["t_enqueued"]


def test_armed_request_record_carries_the_trace_id(net):
    tracing.enable()
    eng = _engine(net)
    try:
        h = eng.submit(PROMPTS[0], 2)
        eng._drive_until([h])
    finally:
        eng.shutdown(drain=False)
    (r,) = tracing.request_records()
    assert r["trace_id"] == h.trace_id is not None
    root = [s for s in tracing.finished_spans(h.trace_id)
            if s.name == "serve.request"][0]
    assert root.attrs["request"] == r["id"]


# -- nothing moves: tokens, counters -------------------------------------------

def test_greedy_tokens_are_unchanged_off_and_armed(net, served):
    """Against one-at-a-time `GPTDecoder.generate`, and again with every
    ledger armed: the instrumentation is host-side only."""
    dec = GPTDecoder(net)
    for p, toks in zip(PROMPTS, served["tokens"]):
        ref = onp.asarray(dec.generate(np.array(p[None, :]), 5))[0]
        assert list(ref[p.size:]) == toks
    tracing.enable()
    anatomy.enable()
    capacity.enable()
    eng = _engine(net)
    try:
        handles, _, _ = _drive(eng, PROMPTS)
        assert [list(h.tokens) for h in handles] == served["tokens"]
    finally:
        eng.shutdown(drain=False)


def test_phase_counters_and_histograms_are_fed_from_the_same_stamps(net):
    registry.reset()
    eng = _engine(net)
    try:
        _drive(eng, PROMPTS[:3])
    finally:
        eng.shutdown(drain=False)
    recs = tracing.step_records()
    for ph in PHASES:
        # the counters count the steps that made no progress too
        assert sched_mod.STEP_SECONDS[ph].value >= sum(r[ph] for r in recs) \
            - 1e-9
    assert sched_mod.STEP_SECONDS["decode_readback"].value == pytest.approx(
        sum(r["decode_readback"] for r in recs))
    rep = registry.report()
    assert rep["mx_serve_submit_lock_wait_seconds"]["count"] == 3
    assert rep["mx_serve_queue_wait_seconds"]["count"] == 3
    assert 'mx_serve_step_seconds_total{phase="idle"}' in rep


def test_the_driver_counts_its_lock_wait_and_its_idle_sleep(net):
    registry.reset()
    eng = _engine(net).start()
    try:
        h = eng.submit(PROMPTS[0], 3)
        assert h.wait(60.0)
        time.sleep(0.02)                      # the driver backs off
    finally:
        eng.shutdown(drain=False)
    assert sched_mod.STEP_SECONDS["idle"].value > 0.0
    assert sched_mod.STEP_SECONDS["lock_wait"].value > 0.0
    assert all(r["lock_wait"] > 0.0 for r in tracing.step_records())


# -- the clock is read once a boundary -----------------------------------------

class _StubSlots:
    """Host arithmetic behind the paged interface (no XLA)."""

    max_slots, max_len = 2, 64
    page_tokens, prefill_chunk = 16, 64

    def __init__(self):
        self.allocator = serve.PageAllocator(9, self.page_tokens)
        self.prefix_cache = serve.PrefixCache(self.allocator)

    def set_slot_pages(self, slot, pages):
        pass

    def clear_slot(self, slot):
        pass

    def prefill_chunk_step(self, slot, chunk_tokens, t_start, key,
                           temperature=1.0):
        return int(t_start) + len(chunk_tokens), len(chunk_tokens), 0

    def fetch_tokens(self, out):
        return onp.asarray(out)

    def fetch_first(self, out):
        return int(out)

    def decode_step(self, last, pos, active, key, temps):
        return onp.where(active, last + 1, last).astype(onp.int32)

    def release(self):
        pass


def _reads_of_a_decode_only_step(count_clock_reads):
    sched = sched_mod.Scheduler(_StubSlots(), max_queue=4)
    sched.submit(_prompt(4), 8)
    sched.step()                              # admit + prefill + decode
    clocks = count_clock_reads(tracing), count_clock_reads(sched_mod)
    assert sched.step() is True               # decode only
    return clocks[0].reads, clocks[1].reads


def test_a_decode_only_step_reads_the_clock_once_a_boundary(count_clock_reads):
    """Start, end of admit, return of the decode call, end of `_launch`'s
    tail (ISSUE 37: the rows' bookkeeping is the launch's, so that the
    read-back is the fetch alone), end of emit, end of the step's own emit,
    end: seven stamps in tracing; in the scheduler the two monotonic
    readings of deadlines and TTFT and nothing else. (The stand-in computes
    on the host: no launch parts, no dry account.)"""
    assert _reads_of_a_decode_only_step(count_clock_reads) == (7, 2)


def test_arming_the_ledgers_adds_no_clock_read_to_the_scheduler(
        count_clock_reads):
    off = _reads_of_a_decode_only_step(count_clock_reads)
    capacity.enable()
    anatomy.enable()
    try:
        armed = _reads_of_a_decode_only_step(count_clock_reads)
    finally:
        capacity.disable()
        capacity.reset()
        anatomy.disable()
        anatomy.reset()
    assert armed[1] == off[1] == 2            # they take the step's stamps


def test_armed_step_spans_take_the_stamped_times():
    tracing.enable()
    sched = sched_mod.Scheduler(_StubSlots(), max_queue=4)
    sched.submit(_prompt(4), 3)
    while sched.step():
        pass
    recs = tracing.step_records()
    steps = [s for s in tracing.finished_spans() if s.name == "serve.step"]
    # every step leaves a span, a progressed one a record too: same stamps
    by_start = {s.t0_ns: s for s in steps}
    for r in recs:
        s = by_start[int(r["t_start"] * 1e9)]
        assert s.dur_ns == pytest.approx(r["wall"] * 1e9, abs=2)
    decode = [s for s in tracing.finished_spans()
              if s.name == "serve.decode_step"]
    assert len(decode) == sum(1 for r in recs if r["decoding"])
    for d in decode:
        parent = [s for s in steps if s.span_id == d.parent_id][0]
        assert parent.t0_ns <= d.t0_ns
        assert d.t0_ns + d.dur_ns <= parent.t0_ns + parent.dur_ns


# -- the profiler's own trace --------------------------------------------------

@pytest.fixture(scope="module")
def host_plane(net, tmp_path_factory):
    """Names of the host events of one profiler session on the CPU: an engine
    with its driver thread, armed, and one `DataParallel.step`."""
    import jax
    from jax.profiler import ProfileData

    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import DataParallel

    out = str(tmp_path_factory.mktemp("xplane"))
    eng = _engine(net)
    eng.generate(PROMPTS[0], 2)               # compiled before the session
    dense = gluon.nn.Dense(1, in_units=4)
    dense.initialize()
    dp = DataParallel(dense, gluon.loss.L2Loss(), mx.optimizer.SGD())
    x = onp.zeros((8, 4), "float32")
    dp.step(np.array(x), np.array(x[:, :1]))
    jax.profiler.start_trace(out)
    tracing.enable()
    try:
        eng.start()
        done = threading.Event()

        def client():
            list(eng.iter_tokens(eng.submit(PROMPTS[1], 3)))
            done.set()

        threading.Thread(target=client).start()
        assert done.wait(60.0)
        time.sleep(0.01)
        dp.step(np.array(x), np.array(x[:, :1]))
    finally:
        tracing.disable()
        eng.shutdown(drain=False)
        jax.profiler.stop_trace()
        tracing.reset()
    data = ProfileData.from_file(
        glob.glob(out + "/plugins/profile/*/*.xplane.pb")[0])
    return {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("name", [
    "mx.serve.lock_wait", "mx.serve.idle", "mx.serve.submit", "mx.serve.step",
    "mx.serve.admit", "mx.serve.prefill.launch", "mx.serve.prefill.readback",
    "mx.serve.decode.launch", "mx.serve.decode.readback", "mx.serve.emit",
    "mx.train.step"])
def test_boundary_is_a_span_in_the_profilers_host_plane(host_plane, name):
    assert name in host_plane


@pytest.mark.parametrize("name", ["serve.request", "serve.queue",
                                  "serve.prefill", "serve.decode"])
def test_armed_request_span_is_in_the_profilers_host_plane(host_plane, name):
    """An armed span opens a TraceAnnotation of its own name, across the
    client's and the driver's thread."""
    assert name in host_plane


def test_spec_decode_path_is_stamped_and_annotated(net, tmp_path):
    import jax
    from jax.profiler import ProfileData

    eng = _engine(net, spec_k=2, draft="ngram")
    try:
        eng.generate(PROMPTS[0], 3)
        tracing.reset()
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.generate(PROMPTS[2], 6)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown(drain=False)
    data = ProfileData.from_file(
        glob.glob(str(tmp_path) + "/plugins/profile/*/*.xplane.pb")[0])
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    assert {"mx.serve.spec.draft.propose", "mx.serve.spec.verify.launch",
            "mx.serve.spec.verify.readback"} <= names
    rounds = [r for r in tracing.step_records() if r["decoding"]]
    assert rounds and all(r["decode_launch"] > 0.0 < r["decode_readback"]
                          for r in rounds)
    assert all(sum(r[ph] for ph in PHASES) >= 0.9 * r["wall"] for r in rounds)


# -- dry intervals and the launch's four parts (ISSUE 37) ----------------------

class _Dev:
    """What a launch returns until it is fetched: not a host array."""

    def __init__(self, value):
        self.value = value


class _DrySlots(_StubSlots):
    """`_StubSlots` that launches like `SlotDecoder` (the phases the dry
    account watches, tokens fetched by the scheduler) and whose
    `device_dry()` the test drives: ``dry`` is what the next probes say."""

    def __init__(self, dry=False):
        super().__init__()
        self.dry, self.probes, self.fetches = dry, 0, 0
        self._prev = onp.zeros(self.max_slots, onp.int32)

    def device_dry(self):
        self.probes += 1
        return self.dry

    def prefill_chunk_step(self, slot, chunk_tokens, t_start, key,
                           temperature=1.0):
        with tracing.phase("mx.serve.prefill.launch",
                           "prefill_launch") as launch:
            launch.site()
            return (_Dev(int(t_start) + len(chunk_tokens)),
                    len(chunk_tokens), 0)

    def decode_step(self, last, pos, active, key, temps):
        with tracing.launch_phase() as boundary:
            boundary()                        # prepare
            boundary()                        # key
            boundary()                        # upload: the launch site
            last = onp.where(last < 0, self._prev, last)
            self._prev = onp.where(active, last + 1, last).astype(onp.int32)
            boundary()                        # dispatch
        return _Dev(self._prev)

    def fetch_tokens(self, out):
        self.fetches += 1
        return out.value

    def fetch_first(self, out):
        self.fetches += 1
        return out.value


def _dry_sched(**kw):
    slots = _DrySlots(**kw)
    return sched_mod.Scheduler(slots, max_queue=8), slots


def _intervals(recs=None):
    recs = tracing.step_records() if recs is None else recs
    return [iv for r in recs for iv in r["dry"]]


def test_a_final_chunks_fetch_opens_chunk_fetch_and_the_next_launch_closes():
    """Opens at the stamp at which `fetch_first` returned, closes at the
    stamp the decode launch's dispatch took: both boundaries the clock held
    anyway, and the record is the iteration's that closed it."""
    sched, slots = _dry_sched()
    sched.submit(_prompt(4), 8)
    assert sched.step() is True
    (r,) = tracing.step_records()
    ((t0, t1, cause),) = r["dry"]
    assert cause == "chunk_fetch" and r["mode"] == "cold"
    fetched = r["t_start"] + r["admit"] + r["prefill_launch"] \
        + r["prefill_readback"]
    assert t0 == pytest.approx(fetched, abs=1e-7)
    # emit (`_prompt_done`), then the launch up to its dispatch's return
    assert t1 - t0 <= r["emit"] + r["decode_launch"] + 1e-9
    assert t1 - t0 >= r["launch_key"] + r["launch_upload"] \
        + r["launch_dispatch"] - 1e-9
    assert r["dry_chunk_fetch"] == pytest.approx(t1 - t0)
    assert r["dry_cold_fetch"] == r["dry_late_launch"] == r["dry_no_work"] \
        == 0.0


def test_a_fetch_with_nothing_behind_it_opens_cold_fetch_while_slots_decode():
    """`settle` fetches the step in flight outside any iteration: the
    device has nothing queued until the next (cold) launch returns."""
    sched, slots = _dry_sched()
    sched.submit(_prompt(4), 8)
    sched.step()
    sched.step()                              # a step in flight, ahead
    tracing.reset()
    before = time.perf_counter()
    sched.settle()
    after = time.perf_counter()
    assert sched._flight is None and sched._dry[1] == "cold_fetch"
    assert sched.step() is True
    (r,) = tracing.step_records()
    ((t0, t1, cause),) = r["dry"]
    assert cause == "cold_fetch" and r["mode"] == "cold"
    assert before <= t0 <= after < r["t_start"] < t1
    assert r["dry_cold_fetch"] == pytest.approx(t1 - t0) and r["chunks"] == 0


def test_a_launch_that_finds_the_device_dry_opens_late_launch():
    """Mode ``ahead``, and the step in flight had finished all the same:
    the interval opens at the end of `launch.upload` (the boundary just
    taken, a lower bound) and closes where the dispatch returned, so it is
    exactly ``launch_dispatch`` long."""
    sched, slots = _dry_sched()
    sched.submit(_prompt(4), 8)
    sched.step()
    tracing.reset()
    slots.dry = True
    assert sched.step() is True
    (r,) = tracing.step_records()
    ((t0, t1, cause),) = r["dry"]
    assert cause == "late_launch" and r["mode"] == "ahead"
    assert t1 - t0 == pytest.approx(r["launch_dispatch"], abs=1e-9)
    assert r["dry_late_launch"] == pytest.approx(t1 - t0)
    assert r["dry_cold_fetch"] == r["dry_no_work"] == 0.0
    slots.dry = False
    sched.step()
    assert tracing.step_records()[-1]["dry"] == []      # in time: none


def test_an_empty_engine_is_no_work_across_iterations_and_charged_once():
    """The last fetch leaves nothing to run: whatever opened the interval,
    it is ``no_work``; it stays open over iterations that make no progress
    (they leave no record) and is charged, whole, to the iteration whose
    launch closed it."""
    sched, slots = _dry_sched()
    sched.submit(_prompt(4), 2)
    while sched.step():
        pass
    assert sched._dry == [pytest.approx(sched._dry[0]), "no_work"]
    opened = sched._dry[0]
    assert all(iv[2] != "no_work" for iv in _intervals())
    for _ in range(3):
        assert sched.step() is False          # nothing to do: no record
    time.sleep(0.01)
    tracing.reset()
    sched.submit(_prompt(4, seed=1), 2)
    assert sched.step() is True
    r = tracing.step_records()[0]
    t0, t1, cause = r["dry"][0]
    assert cause == "no_work" and t0 == opened < r["t_start"] < t1
    assert r["dry_no_work"] == pytest.approx(t1 - t0) and t1 - t0 > 0.01
    while sched.step():
        pass
    assert [iv[2] for iv in _intervals()].count("no_work") == 1


def test_the_first_launch_of_an_idle_engine_is_no_work_the_next_late():
    """Nothing was ever fetched, so nothing is open: the first launch site
    finds the device dry and the iteration began with an empty engine
    (``no_work``); once it has launched, a launch site that finds the device
    dry again was late."""
    sched, slots = _dry_sched(dry=True)
    sched.submit(_prompt(4), 4)
    sched.submit(_prompt(4, seed=1), 4)
    sched.step()
    causes = [iv[2] for iv in _intervals()]
    assert causes[0] == "no_work"
    assert set(causes[1:]) <= {"late_launch", "chunk_fetch"}
    assert "late_launch" in causes or causes.count("chunk_fetch") == 2


def test_every_interval_is_closed_in_order_and_inside_its_record():
    sched, slots = _dry_sched()
    for i in range(4):
        sched.submit(_prompt(4 + i, seed=i), 3 + i)
    flip = 0
    while sched.step():
        flip += 1
        slots.dry = flip % 3 == 0
    recs = tracing.step_records()
    ivs = _intervals(recs)
    assert len(ivs) >= 4
    assert all(t0 < t1 for t0, t1, _ in ivs)
    assert [iv[1] for iv in ivs] == sorted(iv[1] for iv in ivs)
    # no two overlap: one interval is open at a time
    assert all(a[1] <= b[0] for a, b in zip(ivs, ivs[1:]))
    for r in recs:
        for t0, t1, cause in r["dry"]:
            assert cause in tracing.DRY_CAUSES
            assert r["t_start"] <= t1 <= r["t_start"] + r["wall"]


@pytest.mark.parametrize("cause", tracing.DRY_CAUSES)
def test_dry_series_equals_the_records_sums(cause):
    registry.reset()
    sched, slots = _dry_sched()
    sched.submit(_prompt(4), 3)
    flip = 0
    while sched.step():
        flip += 1
        slots.dry = flip % 2 == 1
    sched.submit(_prompt(5, seed=2), 6)
    sched.step()
    sched.step()
    sched.settle()
    while sched.step():
        pass
    recs = tracing.step_records()
    by_field = sum(r["dry_" + cause] for r in recs)
    by_interval = sum(t1 - t0 for t0, t1, c in _intervals(recs) if c == cause)
    assert by_field == pytest.approx(by_interval)
    assert sched_mod.DEVICE_DRY[cause].value == pytest.approx(by_field)
    rep = registry.report()
    assert f'mx_serve_device_dry_seconds_total{{cause="{cause}"}}' in rep
    if cause != "cold_fetch":
        assert by_field > 0.0


def test_dry_and_launch_fields_are_not_phases():
    """A second axis over the same wall: not in `PHASES`, not in the clock's
    ``seconds``, not in `mx_serve_step_seconds_total` (the share of a step's
    wall its phases account for would count them twice)."""
    fields = {"dry_" + c for c in tracing.DRY_CAUSES} \
        | set(tracing.LAUNCH_PARTS)
    assert not fields & set(tracing.PHASES)
    assert set(sched_mod.STEP_SECONDS) == set(tracing.PHASES) | {"idle"}
    assert set(sched_mod.DEVICE_DRY) == set(tracing.DRY_CAUSES)
    sched, slots = _dry_sched(dry=True)
    sched.submit(_prompt(4), 4)
    with tracing.StepClock() as probe:
        pass
    assert set(probe.seconds) == set(tracing.PHASES)
    while sched.step():
        pass
    for r in tracing.step_records():
        assert sum(r[ph] for ph in PHASES) <= r["wall"] + 1e-9, r
    assert sum(r["dry_late_launch"] for r in tracing.step_records()) > 0.0


def test_the_dry_account_adds_no_fetch_and_probes_once_a_launch():
    """The probe is the only thing the account asks of the device, once a
    launch site while no interval is open; every fetch is one the loop made
    before (a first token a request, the tokens of each decode step)."""
    sched, slots = _dry_sched()
    sched.submit(_prompt(4), 6)
    sched.step()                              # chunk + fetch + cold launch
    assert (slots.probes, slots.fetches) == (1, 1)   # open at the launch: 0
    sched.step()                              # ahead: one probe, one fetch
    assert (slots.probes, slots.fetches) == (2, 2)
    import inspect
    from incubator_mxnet_tpu.serve import engine as engine_mod
    src = inspect.getsource(engine_mod.SlotDecoder.device_dry)
    assert "is_ready()" in src
    assert "block_until_ready" not in src and "asarray" not in src
    assert "block_until_ready" not in inspect.getsource(tracing)
    assert "block_until_ready" not in inspect.getsource(sched_mod)


def test_a_host_computing_stand_in_keeps_no_dry_account():
    sched = sched_mod.Scheduler(_StubSlots(), max_queue=4)
    sched.submit(_prompt(4), 4)
    while sched.step():
        pass
    recs = tracing.step_records()
    assert all(r["dry"] == [] for r in recs)
    assert all(r["dry_" + c] == 0.0 for r in recs for c in tracing.DRY_CAUSES)
    assert sched._dry == [None, None]


def test_launch_parts_of_a_stand_in_add_up_to_the_launch():
    """The four parts and nothing else between the launch's first and last
    boundary but the engine's counters; the scheduler's bookkeeping of the
    rows it launched is ``launch_prepare``'s."""
    sched, slots = _dry_sched()
    sched.submit(_prompt(4), 6)
    while sched.step():
        pass
    launched = [r for r in tracing.step_records() if r["decoding"]]
    assert launched
    for r in launched:
        parts = sum(r[p] for p in tracing.LAUNCH_PARTS)
        assert all(r[p] > 0.0 for p in tracing.LAUNCH_PARTS)
        assert parts <= r["decode_launch"] + 1e-9
        assert r["decode_launch"] - parts < 2e-4       # one phase's exit


# the real engine on the CPU

def test_real_launch_parts_add_up_to_decode_launch(served):
    recs = [r for r in tracing.step_records() if r["decoding"]]
    for r in recs:
        assert all(r[p] > 0.0 for p in tracing.LAUNCH_PARTS), r
        assert sum(r[p] for p in tracing.LAUNCH_PARTS) \
            <= r["decode_launch"] + 1e-9
    rest = [r for r in tracing.step_records() if not r["decoding"]]
    assert all(r[p] == 0.0 for r in rest for p in tracing.LAUNCH_PARTS)
    # leaving out the first launch (it builds and compiles the program)
    parts = sum(r[p] for r in recs[1:] for p in tracing.LAUNCH_PARTS)
    assert parts >= 0.8 * sum(r["decode_launch"] for r in recs[1:])


def test_mode_and_the_dry_causes_agree(served):
    """``ahead``: something was in flight, so no interval that a fetch with
    nothing behind it or an empty engine opened can end there; ``cold``: the
    device waited for this launch, and the record says since when."""
    recs = tracing.step_records()
    for r in recs:
        if r["mode"] == "ahead":
            assert r["dry_cold_fetch"] == r["dry_no_work"] == 0.0, r
        if r["mode"] == "cold":
            assert r["dry"], r
            assert r["dry_chunk_fetch"] + r["dry_cold_fetch"] \
                + r["dry_no_work"] > 0.0, r
    assert any(r["mode"] == "cold" for r in recs)
    assert all(t0 < t1 for t0, t1, _ in _intervals(recs))


def test_a_real_final_chunk_yields_exactly_one_chunk_fetch_interval(net):
    """GPT-2 tiny through `ServeEngine`: a 40-token prompt alone is three
    chunks, and only the last is fetched: one drain, in that iteration."""
    eng = _engine(net)
    try:
        eng.generate(PROMPTS[0], 2)           # compiled, pools made
        tracing.reset()
        h = eng.submit(PROMPTS[1], 3)
        while not h.done:
            eng.step()
    finally:
        eng.shutdown(drain=False)
    recs = tracing.step_records()
    drains = [(r, iv) for r in recs for iv in r["dry"]
              if iv[2] == "chunk_fetch"]
    assert len(drains) == 1
    r, (t0, t1, _) = drains[0]
    assert r["prefill_readback"] > 0.0 and r["mode"] == "cold"
    assert r["t_start"] < t0 < t1 <= r["t_start"] + r["wall"]
    # the engine had been empty before the request came
    assert recs[0]["dry"][0][2] == "no_work"


def _reads_of_a_real_decode_only_step(net, count_clock_reads):
    eng = _engine(net)
    try:
        sched = eng._sched
        probes = []
        dry = sched._device_dry
        sched._device_dry = lambda: probes.append(1) or dry()
        sched.submit(PROMPTS[0], 8)
        sched.step()                          # admit + chunk + cold launch
        sched.step()                          # ahead: programs compiled
        clocks = count_clock_reads(tracing), count_clock_reads(sched_mod)
        del probes[:]
        assert sched.step() is True           # decode only
        r = tracing.step_records()[-1]
        assert r["chunks"] == 0 and r["mode"] == "ahead"
        return clocks[0].reads, clocks[1].reads, len(probes)
    finally:
        eng.shutdown(drain=False)


def test_a_real_decode_only_step_reads_the_clock_twelve_times(
        net, count_clock_reads):
    """The parent's seven (start, end of admit, end of the launch, end of
    the read-back, end of emit twice, end; the stand-in above has no launch
    phase of its own) and five more: the ends of `launch.prepare`, `.key`,
    `.upload` and `.dispatch`, and the end of `_launch`'s tail. At most
    seven more were allowed, and one `is_ready()`."""
    reads, sched_reads, probes = _reads_of_a_real_decode_only_step(
        net, count_clock_reads)
    assert reads == 7 + 5 <= 7 + 7
    assert sched_reads == 2 and probes == 1
