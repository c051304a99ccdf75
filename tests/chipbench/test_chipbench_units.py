"""The benchmark's own arithmetic, each piece against a count made by hand:
the load generator, the window, the operation counts, the interval
reduction. No device, no program."""
import math
import threading

import numpy as onp
import pytest

import cb_tiny  # noqa: F401  (puts the checkout on sys.path)
from chipbench.flops import bert as bert_flops
from chipbench.flops import gpt as gpt_flops
from chipbench.lib import loadgen, trace
from chipbench.runners import train

CHAT = cb_tiny.FILES["traffic/chat-tiny.json"]


def take(traffic, seed, n, vocab=500):
    stream = loadgen.requests(traffic, seed, vocab)
    return [next(stream) for _ in range(n)]


def test_generator_is_deterministic_in_the_seed():
    a, b = take(CHAT, 2**31 + 7, 30), take(CHAT, 2**31 + 7, 30)
    assert all(onp.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.gap_s == y.gap_s for x, y in zip(a, b))
    c = take(CHAT, 8, 30)
    assert any(not onp.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    n, r = CHAT["sizes"], CHAT["ramp_sizes"]
    for cycle in (slice(0, r), slice(r, r + n), slice(r + n, r + 2 * n)):
        a, b = take(CHAT, 1, r + 2 * n)[cycle], take(CHAT, 2, r + 2 * n)[cycle]
        assert {x.ramp for x in a} == {cycle.start == 0}
        key = lambda r: (r.prompt.size, r.max_new, r.shared)  # noqa: E731
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert sorted(r.gap_s for r in a) == sorted(r.gap_s for r in b)
        assert [key(r) for r in a] != [key(r) for r in b]
    assert math.isclose(sum(loadgen.gap_set(CHAT)) / n, 1 / CHAT["rate_rps"])
    assert math.isclose(sum(r.gap_s for r in take(CHAT, 1, r)), CHAT["ramp_s"])


def test_a_window_as_long_as_a_cycle_gets_the_cycles_requests():
    """After the ramp cycle the generator holds the first full cycle until
    the runner says when the window opened (here 0.4 s late, as a profiler's
    start makes it), and counts its places from there: a window of
    `sizes / rate_rps` seconds then holds exactly `sizes` due times, none on
    its edges, whatever the seed."""
    n, cycle_s = CHAT["sizes"], CHAT["sizes"] / CHAT["rate_rps"]
    for seed in (1, 2, 2**31 + 5):
        now = [10.0]

        def sleep(dt):
            now[0] += dt

        client = loadgen.Client(FakeEngine(), clock=lambda: now[0])
        stream = iter(take(CHAT, seed, CHAT["ramp_sizes"] + 2 * n))
        gen = loadgen.Generator(client, CHAT, stream, clock=lambda: now[0],
                                sleep=sleep)
        t_open = 10.0 + CHAT["ramp_s"] + 0.4
        gen.open(t_open)
        gen.run()
        client.join(5.0)
        ramp = [r for r in client.sent if r.ramp]
        assert len(ramp) == CHAT["ramp_sizes"]
        assert all(10.0 < r.due < 10.0 + CHAT["ramp_s"] for r in ramp)
        for k in (0, 1):
            lo = t_open + k * cycle_s
            due = [r for r in client.sent if lo <= r.due < lo + cycle_s]
            assert len(due) == n and not any(r.ramp for r in due)
            assert min(r.due for r in due) - lo >= loadgen.lead_s(CHAT) - 1e-9
            assert lo + cycle_s - max(r.due for r in due) >= \
                loadgen.lead_s(CHAT) - 1e-9


def test_sizes_keep_to_the_stated_range_and_share():
    sizes = loadgen.size_set(CHAT)
    assert all(CHAT["output"]["lo"] <= o <= CHAT["output"]["hi"]
               for _, o, _ in sizes)
    assert sum(s for _, _, s in sizes) == len(sizes) // 2
    pre = CHAT["shared_prefix"]["tokens"]
    reqs = take(CHAT, 5, 30)
    shared = [r for r in reqs if r.shared]
    assert shared and all(
        onp.array_equal(r.prompt[:pre], shared[0].prompt[:pre]) for r in shared)
    assert all(r.prompt.size > pre for r in shared)


class FakeEngine:
    """Accepts everything; a request's only token comes out at once."""
    queue_depth = 0

    def submit(self, prompt, max_new):
        return object()

    def iter_tokens(self, handle, timeout=None):
        yield 1


def test_open_loop_times_from_the_due_time_and_reports_lateness():
    now = [100.0]

    def sleep(dt):          # the host stalls: every sleep overshoots 30 ms
        now[0] += dt + 0.03

    client = loadgen.Client(FakeEngine(), clock=lambda: now[0])
    gen = loadgen.Generator(client, CHAT, iter(take(CHAT, 1, 5)),
                            clock=lambda: now[0], sleep=sleep)
    gen.run()               # in this thread: the stream ends after 5
    dues = [r.due for r in client.sent]
    want, t = [], 100.0 - loadgen.lead_s(CHAT)
    for r in client.sent:
        t += r.gap_s
        want.append(t)
    assert dues == pytest.approx(want)      # the schedule, not the submit
    client.join(5.0)
    assert all(r.started >= r.due and r.done.is_set() for r in client.sent)
    assert min(gen.late_s) >= 0 and max(gen.late_s) >= 0.03 - 1e-9


def test_a_submit_the_engine_holds_up_delays_no_other_arrival():
    """Independent users: request 0's `submit` blocks until the test lets it
    go; the four behind it are submitted on time all the same, and request
    0's time to first token counts its wait."""
    gate = threading.Event()

    class Eng(FakeEngine):
        def __init__(self):
            self.calls = 0

        def submit(self, prompt, max_new):
            self.calls += 1
            if self.calls == 1:
                gate.wait(5.0)
            return object()

    client = loadgen.Client(Eng())
    fast = dict(CHAT, rate_rps=200.0)
    gen = loadgen.Generator(client, fast, iter(take(fast, 1, 5)))
    gen.run()
    for r in client.sent[1:]:
        assert r.done.wait(5.0)
    first = client.sent[0]
    assert not first.done.is_set() and first.submitted is None
    gate.set()
    client.join(5.0)
    assert first.done.is_set()
    assert first.token_times[0] - first.due > max(
        r.token_times[0] - r.due for r in client.sent[1:])
    assert max(gen.late_s) < 0.5


class FakeLoop:
    """A loop whose step takes 0.3 s of a fake clock."""

    def __init__(self, now):
        self.now, self.steps, self.drains = now, 7, 0

    def one(self):
        self.now[0] += 0.3
        self.steps += 1

    def drain(self):
        self.now[0] += 0.05
        self.drains += 1


def test_window_counts_whole_steps_over_elapsed_time():
    now = [50.0]
    loop = FakeLoop(now)
    steps, t_open, t_close = train.timed_window(loop, 2.0,
                                                clock=lambda: now[0])
    # 7 launches bring the clock to 2.1 >= 2.0; the drain adds 0.05
    assert steps == 7 and loop.drains == 2
    assert t_close - t_open == pytest.approx(7 * 0.3 + 0.05)
    rate = steps * 100 / (t_close - t_open)
    assert rate != pytest.approx(steps * 100 / 2.0)   # not steps / nominal


def test_batches_differ_by_step_and_row():
    x1, y1 = train.batch_of(3, 1, 4, 8, 500)
    x2, _ = train.batch_of(3, 2, 4, 8, 500)
    assert not onp.array_equal(x1, x2) and not onp.array_equal(x1, y1)
    assert len({tuple(r) for r in x1}) == 4
    assert onp.array_equal(x1, train.batch_of(3, 1, 4, 8, 500)[0])


GPT2XL = {"n_layer": 48, "n_embd": 1600, "n_head": 25, "vocab_size": 50257}
BERT = {"num_hidden_layers": 12, "hidden_size": 768, "intermediate_size": 3072,
        "vocab_size": 30522}


def test_gpt_flops_against_hand_counts():
    per_layer = 3 * 1600 * 1600 + 1600 * 1600 + 2 * 1600 * 6400
    assert gpt_flops.layer_matmul_params(GPT2XL) == 48 * per_layer == 1474560000
    # one decoded token at context 100: matmuls, attention, head
    want = 2 * 48 * per_layer + 48 * 4 * 100 * 1600 + 2 * 1600 * 50257
    assert gpt_flops.token_flops(GPT2XL, 100, True) == want
    # a prompt's positions [16, 80) equal the sum of its tokens, head once
    by_token = sum(gpt_flops.token_flops(GPT2XL, p + 1, False)
                   for p in range(16, 80)) + 2 * 1600 * 50257
    assert gpt_flops.prompt_flops(GPT2XL, 16, 80) == by_token
    # bytes: fp32 weights ~6.2 GB once a step; KV 614,400 B a live token
    assert gpt_flops.kv_bytes_per_token(GPT2XL, 4) == 2 * 48 * 1600 * 4 == 614400
    assert 6.1e9 < gpt_flops.weight_bytes(GPT2XL, 4) < 6.3e9
    f, b = gpt_flops.decode_step(GPT2XL, [100, 300], 4)
    assert f == gpt_flops.token_flops(GPT2XL, 100, True) \
        + gpt_flops.token_flops(GPT2XL, 300, True)
    assert b == gpt_flops.weight_bytes(GPT2XL, 4) + 614400 * 400


def test_bert_flops_against_hand_counts():
    per_layer = 2 * (4 * 768 * 768 + 2 * 768 * 3072) + 4 * 512 * 768
    head = 2 * 768 * 768 + 2 * 768 * 30522
    assert bert_flops.forward_flops_per_token(BERT, 512) == 12 * per_layer + head
    assert bert_flops.train_flops_per_token(BERT, 512) == 3 * (12 * per_layer + head)
    # ~0.67 GFLOP a token: 6 x (85 M layer + 24 M head parameters) + attention
    assert 6.4e8 < bert_flops.train_flops_per_token(BERT, 512) < 7.2e8


def test_interval_arithmetic():
    assert trace.union([[5, 9], [0, 3], [2, 4], [9, 9]]) == [[0, 4], [5, 9]]
    assert trace.total([[0, 4], [5, 9]]) == 8
    assert trace.clip([[0, 4], [5, 9]], 3, 6) == [[3, 4], [5, 6]]
    assert trace.subtract([[0, 10], [20, 30]], [[5, 22], [25, 26]]) == \
        [[0, 5], [22, 25], [26, 30]]
    assert trace.op_family("%fusion.123 = f32[8]") == "fusion"
    assert trace.op_family("copy-done.7") == "copy-done"
    assert trace.percentile([1, 2, 3, 4], 50) == 2.5


def test_reduction_reads_one_lane_and_never_adds_nested_ones():
    """A step of 300 ns holding one program of 250 ns holding three kernels
    of 200 ns in all: busy is 200, not 750."""
    lanes = {"devices": {"/device:TPU:0": {
        "Steps": [["1", 100, 300]],
        "XLA Modules": [["jit_step(9)", 120, 250]],
        "XLA Ops": [["fusion.1", 120, 80], ["custom-call.2", 210, 40],
                    ["fusion.3", 290, 80]]}},
        "host": [["cb.window", 100, 400], ["cb.train.step", 195, 20]]}
    t = trace.Trace(lanes)
    assert t.window_s == pytest.approx(400e-9)
    assert t.busy_s() == pytest.approx(200e-9)
    assert t.idle_share() == pytest.approx(0.5)
    assert t.module_ms("step") == [pytest.approx(250e-6)]
    assert t.op_seconds("custom-call") == pytest.approx(40e-9)
    assert t.top_ops() == [["fusion", pytest.approx(160e-9)],
                           ["custom-call", pytest.approx(40e-9)]]
    gaps = dict(t.idle_gaps())
    assert gaps["host:cb.train.step"] == pytest.approx(10e-9)   # 200..210
    assert gaps["between:custom-call_fusion"] == pytest.approx(40e-9)
    assert sum(gaps.values()) == pytest.approx(200e-9)


def test_client_threads_end():
    client = loadgen.Client(FakeEngine())
    reqs = [client.submit(r) for r in take(CHAT, 1, 4)]
    client.join(5.0)
    assert all(r.done.is_set() and r.tokens == [1] for r in reqs)
    assert not any(t.is_alive() for t in client._threads)
    assert threading.active_count() < 20


RECORDED = cb_tiny.os.path.join(cb_tiny.harness.CHIPBENCH, "testdata",
                                "bert_seq512.lanes.json.gz")


def test_reduction_on_a_recorded_chip_trace():
    """Two BERT-base steps recorded on a v5e (PR 24): the numbers below were
    taken from the file by hand (a sort and a sweep over the 9,584 op events;
    two module events; the kernels whose custom-call target is
    tpu_custom_call)."""
    lanes = trace.read_lanes(RECORDED)
    dev = lanes["devices"]["/device:TPU:0"]
    assert {k: len(v) for k, v in dev.items()} == {
        "Steps": 2, "XLA Modules": 2, "XLA Ops": 9584}
    # by hand: a sweep over the sorted op intervals
    busy, end = 0, 0
    for s, d in sorted((s, d) for _, s, d in dev["XLA Ops"]):
        busy += max(0, s + d - max(s, end))
        end = max(end, s + d)
    assert busy == 255225755
    t = trace.Trace(lanes)
    assert t.window_s == pytest.approx(0.257264387)
    assert t.busy_s() == pytest.approx(0.255225755)
    assert t.idle_share() == pytest.approx(1 - 0.255225755 / 0.257264387)
    assert t.module_ms("step") == [pytest.approx(127.632408),
                                   pytest.approx(127.625222)]
    assert t.module_seconds("step") == pytest.approx(0.25525763)
    assert t.op_seconds("tpu_custom_call") == pytest.approx(0.018205916)
    assert t.top_ops(2)[0] == ["fusion", pytest.approx(0.109154126)]
    # what summing the nested lanes would claim: three times the truth
    nested = sum(d for lane in dev.values() for _, _, d in lane)
    assert nested == 765747772 and nested > 2.99 * busy
    idle = t.window_s - t.busy_s()      # the ten longest kinds of gap
    assert 0.99 * idle < sum(g for _, g in t.idle_gaps()) <= idle


def test_reduction_on_a_recorded_serving_trace():
    """One engine step of GPT-2 XL recorded on a v5e (PR 24, the long-document
    backlog): three prefill chunks and one decode step under the benchmark's
    own spans. By hand: a sweep over the 11,347 op events gives 377,520,446 ns
    busy; the four programs' events; `copy` is the largest op family."""
    lanes = trace.read_lanes(cb_tiny.os.path.join(
        cb_tiny.harness.CHIPBENCH, "testdata", "gpt2xl_serve.lanes.json.gz"))
    dev = lanes["devices"]["/device:TPU:0"]
    assert len(dev["XLA Ops"]) == 11347 and "Steps" not in dev
    busy, end = 0, 0
    for s, d in sorted((s, d) for _, s, d in dev["XLA Ops"]):
        busy += max(0, s + d - max(s, end))
        end = max(end, s + d)
    assert busy == 377520446
    t = trace.Trace(lanes)
    assert t.window_s == pytest.approx(0.414908107)
    assert t.busy_s() == pytest.approx(0.377520446)
    # the lanes nested: adding the programs' lane would double the time
    nested = sum(d for _, _, d in dev["XLA Modules"]) / 1e9
    assert nested == pytest.approx(0.3776, abs=2e-3) and t.busy_s() < 0.38
    assert t.module_ms("prefill") == pytest.approx(
        [72.879543, 73.114267, 72.997233])
    assert t.module_ms("decode") == pytest.approx([158.582246])
    assert t.top_ops(2) == [["copy", pytest.approx(0.182700533)],
                            ["fusion", pytest.approx(0.102494044)]]
    gaps = dict(t.idle_gaps())
    # the device waits while the host is between a chunk's launch and result
    assert gaps["host:cb.serve.prefill_chunk"] == pytest.approx(0.022039504)
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s())


def test_op_label_keeps_the_name_and_the_custom_call_target():
    text = ('%jvp__.26 = (f32[16384,768]{1,0}) custom-call(f32[16384,768] '
            '%convert_add_fusion, f32[1,768] %custom-call.5), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert trace.op_label(text) == "jvp__.26 tpu_custom_call"
    assert trace.op_label('%copy.390 = bf16[768,768] copy(f32[768,768] '
                          '%custom-call.170)') == "copy.390"
    assert trace.op_family(text) == "jvp__"
