"""The program's own timeline read by the benchmark (ISSUE 25): seven
`program_span` metrics over `telemetry.tracing`'s step and request records,
through the harness at a toy size; and, on lanes recorded on the chip with the
program's `mx.serve.*` spans in the host list, where the device's idle time
falls and how far the host's read-back lies from the device's clock."""
import json
import os

import pytest

import cb_tiny
from chipbench.lib import harness, trace
from chipbench.readers import program_requests, program_steps

SEVEN = ["decode_launch_ms_p50.itl", "decode_readback_ms_p50.itl",
         "step_host_ms_p50.itl", "step_accounted_share.itl",
         "submit_lock_wait_ms_p50.chat", "queue_wait_ms_p50.chat",
         "prefill_phase_ms_p50.chat"]
LANES = os.path.join(harness.CHIPBENCH, "testdata",
                     "gpt2xl_serve_spans.lanes.json.gz")
# read off the recording by hand (my chip run, PR 25)
RECORDED = {"any_span": 0.9953493585591713, "leaves": 0.9776519195992391,
            "after_p50": 2.8679015, "largest_gap": "host:mx.serve.prefill.readback"}


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    """A toy cell that reports the seven, added as one new file."""
    root = cb_tiny.make_root(tmp_path_factory.mktemp("cb_spans"))
    with open(os.path.join(root, "workloads", "tiny.spans.json"), "w") as f:
        json.dump({"config": "gpt-tiny", "traffic": "chat-tiny", "chips": 1,
                   "end_to_end": ["itl_p50_ms", "setup_s", *SEVEN],
                   "per_layer": ["prefix_hit_share.itl"],
                   "limits": cb_tiny.SERVE_LIMITS}, f)
    return cb_tiny.run(root, "tiny.spans")


@pytest.mark.parametrize("name", SEVEN)
def test_toy_cell_reports_the_metric_through_the_harness(result, name):
    """Read after the runner shut the engine down, freed it and deleted it."""
    assert result["correct"] is True
    m = result["metrics"][name]
    assert m["unit"] == ("%" if name == "step_accounted_share.itl" else "ms")
    assert m["value"] >= 0.0


def test_toy_readings_hang_together(result):
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert 90.0 <= m["step_accounted_share.itl"] <= 100.0
    # a step's host remainder is a part of a step; a gap between tokens holds
    # at least the launch and the read-back of one decode step
    assert m["decode_launch_ms_p50.itl"] + m["decode_readback_ms_p50.itl"] \
        <= 1.5 * m["itl_p50_ms"]
    assert m["prefill_phase_ms_p50.chat"] > 0.0


@pytest.mark.parametrize("name", SEVEN)
def test_metric_file_names_a_layer_the_benchmark_has(name):
    bench = json.load(open(os.path.join(cb_tiny.ROOT, "BENCHMARK.json")))
    m = harness.Spec(bench["workloads"][0]["name"]).metric(name)
    assert m["source"] == "program_span" and m["moves"] == "itl_p50_ms"
    assert m["layer"] in {e["layer"] for e in bench["per_layer"]}
    assert m["reader"] in ("program_steps", "program_requests")
    assert name not in {e["name"] for e in bench["per_layer"]}   # not wired yet


WINDOW = {"window": {"t_open": 10.0, "t_close": 20.0}}


def _step(t, **kw):
    rec = dict.fromkeys(("lock_wait", "admit", "prefill_launch",
                         "prefill_readback", "decode_launch",
                         "decode_readback", "emit"), 0.0)
    rec.update(t_start=t, chunks=0, decoding=0, prefilling=0, queued=0, **kw)
    return rec


def test_step_reader_windows_takes_percentiles_and_shares(monkeypatch):
    from incubator_mxnet_tpu.telemetry import tracing

    recs = [_step(9.9, wall=1.0, decode_launch=0.5),          # before the open
            _step(10.0, wall=0.100, decode_launch=0.002,
                  decode_readback=0.090, admit=0.001, emit=0.006),
            _step(12.0, wall=0.200, decode_launch=0.004,
                  decode_readback=0.180, prefill_launch=0.010, emit=0.004),
            _step(15.0, wall=0.050, prefill_launch=0.020,
                  prefill_readback=0.029),                    # no decode
            _step(20.0, wall=1.0, decode_launch=0.5)]         # at the close
    monkeypatch.setattr(
        tracing, "step_records", lambda since=None, until=None: [
            r for r in recs if since <= r["t_start"] < until])
    read = program_steps.read
    assert read(WINDOW, "decode_launch", q=50) == pytest.approx(3.0)
    assert read(WINDOW, "decode_readback", q=100) == pytest.approx(180.0)
    four = ["prefill_launch", "prefill_readback", "decode_launch",
            "decode_readback"]
    # 100 - 92, 200 - 194, 50 - 49: the median
    assert read(WINDOW, "wall", q=50, minus=four) == pytest.approx(6.0)
    phases = ["admit", *four, "emit"]
    assert read(WINDOW, phases, per="wall") == pytest.approx(
        100.0 * (0.099 + 0.198 + 0.049) / 0.350)
    assert read({"window": {"t_open": 30.0, "t_close": 40.0}},
                "decode_launch", q=50) is None


def test_request_reader_windows_by_the_submit_call(monkeypatch):
    from incubator_mxnet_tpu.telemetry import tracing

    recs = [{"t_submit_call": 9.0, "t_enqueued": 9.5, "t_admit": 9.6},
            {"t_submit_call": 11.0, "t_enqueued": 11.1, "t_admit": 11.4},
            {"t_submit_call": 12.0, "t_enqueued": 12.3, "t_admit": None},
            {"t_submit_call": 19.0, "t_enqueued": 19.2, "t_admit": 21.0}]
    monkeypatch.setattr(
        tracing, "request_records", lambda since=None, until=None: [
            r for r in recs if since <= r["t_submit_call"] < until])
    read = program_requests.read
    assert read(WINDOW, "t_submit_call", "t_enqueued", 50) == \
        pytest.approx(200.0)
    # a request that was never admitted has no queue wait to read
    assert read(WINDOW, "t_enqueued", "t_admit", 0) == pytest.approx(300.0)
    assert read(WINDOW, "t_enqueued", "t_admit", 100) == pytest.approx(1800.0)


def test_readers_read_nothing_from_a_program_without_the_records(monkeypatch):
    """The parent of ISSUE 25 has no `step_records`: the metric is left out."""
    from incubator_mxnet_tpu.telemetry import tracing

    monkeypatch.delattr(tracing, "step_records")
    monkeypatch.delattr(tracing, "request_records")
    assert program_steps.read(WINDOW, "decode_launch", q=50) is None
    assert program_requests.read(WINDOW, "t_enqueued", "t_admit", 50) is None


# -- lanes recorded on the chip, the program's spans in the host list ---------

@pytest.fixture(scope="module")
def recorded():
    """2.4 s of a traced `gpt2xl.chat` run on a v5e (PR 25), cut to whole
    scheduler steps (10 of them, each a prefill chunk and a decode launch): the
    device's lanes and the host's `mx.serve.*` and `cb.*` spans, as
    `load_lanes` keeps them with `SPAN_PREFIX = ("cb.", "mx.")`."""
    lanes = trace.read_lanes(LANES)
    t = trace.Trace(lanes)
    dev = sorted(t.devices)[0]
    idle = trace.subtract([[t.lo, t.hi]], t.busy_intervals(dev))
    spans = [(n, s, s + d) for n, s, d in lanes["host"]
             if n.startswith("mx.serve.")]
    return lanes, t, idle, spans


def covered(idle, intervals):
    """The share of `idle` that lies under the union of `intervals`."""
    under = trace.union([list(iv) for iv in intervals])
    return 1.0 - trace.total(trace.subtract(idle, under)) / trace.total(idle)


def test_recording_holds_the_programs_spans_beside_the_benchmarks(recorded):
    lanes, t, _, spans = recorded
    names = {n for n, _, _ in spans}
    assert names >= {"mx.serve.step", "mx.serve.lock_wait", "mx.serve.admit",
                     "mx.serve.prefill.launch", "mx.serve.prefill.readback",
                     "mx.serve.decode.launch", "mx.serve.decode.readback",
                     "mx.serve.emit", "mx.serve.submit"}
    assert any(n.startswith("cb.serve.") for n, _, _ in lanes["host"])
    assert 2.0 < t.window_s < 3.0 and 0.03 < t.idle_share() < 0.10


def test_recorded_idle_time_falls_under_the_programs_spans(recorded):
    """At least 90 % of the time the device stood idle lies under one of the
    program's spans, and under one of its phases (a leaf, not the step's own
    span): what is left over names the boundary that is missing."""
    _, _, idle, spans = recorded
    any_span = covered(idle, [(s, e) for _, s, e in spans])
    leaves = covered(idle, [(s, e) for n, s, e in spans
                            if n != "mx.serve.step"])
    assert any_span >= 0.90 and leaves >= 0.90
    assert (any_span, leaves) == (pytest.approx(RECORDED["any_span"]),
                                  pytest.approx(RECORDED["leaves"]))


def test_recorded_idle_gaps_are_named_by_the_programs_phases(recorded):
    """`Trace.idle_gaps` as it stands charges a gap to the innermost span
    open when it begins: with `mx.` spans in the list those are the
    program's phases, no longer the benchmark's three wrappers."""
    lanes, _, idle, _ = recorded
    only_mx = dict(lanes, host=[e for e in lanes["host"]
                                if e[0].startswith("mx.")])
    gaps = dict(trace.Trace(only_mx).idle_gaps())
    named = sum(v for k, v in gaps.items() if k.startswith("host:mx.serve."))
    assert named >= 0.9 * trace.total(idle) / 1e9
    assert max(gaps, key=gaps.get) == RECORDED["largest_gap"]


def test_recorded_readbacks_end_just_after_their_decode_program(recorded):
    """The host's spans and the device's lanes share a clock, shown and not
    assumed: each `mx.serve.decode.readback` ends a millisecond or two after
    the decode program it waited for ends in ``XLA Modules`` (a clock that is
    not shared reads tens of milliseconds, or seconds)."""
    lanes, t, _, spans = recorded
    dev = sorted(t.devices)[0]
    ends = sorted(s + d for n, s, d in t.devices[dev][trace.MODULES]
                  if "decode" in n)
    after = []
    for n, s, e in spans:
        if n == "mx.serve.decode.readback":
            waited_for = [m for m in ends if s < m <= e + 1e6]
            assert len(waited_for) == 1
            after.append((e - waited_for[0]) / 1e6)
    assert len(after) >= 10
    assert 0.0 < trace.percentile(after, 50) < 3.0
    assert trace.percentile(after, 95) < 5.0
    assert trace.percentile(after, 50) == pytest.approx(RECORDED["after_p50"])
