"""The program's account of when the device stood dry, read by the benchmark
(ISSUE 37): `program_dry` over the step records' ``dry_<cause>`` fields,
`idle_explained` over their ``dry`` intervals mapped onto the trace's clock
through the harness's one anchor, and the launch's four parts through the
`program_steps` reader as it is. Synthetic lanes and records for the
arithmetic, a toy cell through the harness for the wiring."""
import json
import os

import pytest

import cb_tiny
from chipbench.lib import harness, trace
from chipbench.readers import idle_explained, program_dry, program_steps

DRY = ["host_dry_share.itl", "dry_chunk_fetch_share.itl",
       "dry_late_launch_share.itl", "no_work_share.itl"]
PARTS = [f"launch_{p}_ms_p50.itl"
         for p in ("prepare", "key", "upload", "dispatch")]
NINE = DRY + ["idle_explained_share.itl"] + PARTS
CAUSES = ("chunk_fetch", "cold_fetch", "late_launch", "no_work")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(cb_tiny.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the files -----------------------------------------------------------------

@pytest.mark.parametrize("name", NINE)
def test_metric_file_loads_and_names_a_reader_that_exists(bench, name):
    m = harness.Spec(bench["workloads"][0]["name"]).metric(name)
    assert set(m) == {"layer", "unit", "better", "source", "moves", "reader",
                      "params"}
    assert m["moves"] == "itl_p50_ms"
    assert m["layer"] in {e["layer"] for e in bench["per_layer"]}
    assert callable(harness.module_of("readers", m["reader"]).read)
    if name in DRY:
        assert (m["reader"], m["unit"], m["better"], m["source"]) == \
            ("program_dry", "%", "lower", "program_span")
        assert m["layer"].startswith("front door and scheduler")
    elif name in PARTS:
        assert (m["reader"], m["unit"], m["better"], m["source"]) == \
            ("program_steps", "ms", "lower", "program_span")
        assert m["params"] == {"field": name[:-len("_ms_p50.itl")], "q": 50}
        assert m["layer"] == "program families (serve/engine.py)"
    else:
        assert (m["reader"], m["unit"], m["better"], m["source"],
                m["layer"]) == ("idle_explained", "%", "higher",
                                "device_trace", "device")


def test_host_dry_share_is_the_three_causes_the_host_is_answerable_for():
    spec = harness.Spec("gpt2xl.chat")
    assert spec.metric("host_dry_share.itl")["params"]["cause"] == \
        ["chunk_fetch", "cold_fetch", "late_launch"]
    assert spec.metric("no_work_share.itl")["params"]["cause"] == "no_work"


def test_the_nine_wait_for_a_benchmark_pr_to_wire_them(bench):
    """An accepted cell's file and `BENCHMARK.json`'s entries are a
    `benchmark` PR's to edit (ROADMAP S1a has the lines to append): until
    then the names are in neither, and no name is taken twice."""
    listed = [e["name"] for e in bench["per_layer"] + bench["end_to_end"]]
    assert len(set(listed)) == len(listed)
    assert not set(NINE) & set(listed)
    for cell in bench["workloads"]:
        spec = harness.Spec(cell["name"])
        assert not set(NINE) & set(spec.cell["per_layer"]
                                   + spec.cell["end_to_end"])


# -- through the harness at a toy size ---------------------------------------

@pytest.fixture(scope="module")
def result(tmp_path_factory):
    """A toy cell that reports the eight that need no trace, as one new
    file (untraced: the step records are always on)."""
    root = cb_tiny.make_root(tmp_path_factory.mktemp("cb_dry"))
    with open(os.path.join(root, "workloads", "tiny.dry.json"), "w") as f:
        json.dump({"config": "gpt-tiny", "traffic": "chat-tiny", "chips": 1,
                   "end_to_end": ["itl_p50_ms", "setup_s", *DRY, *PARTS,
                                  "decode_launch_ms_p50.itl",
                                  "idle_explained_share.itl"],
                   "per_layer": ["prefix_hit_share.itl"],
                   "limits": cb_tiny.SERVE_LIMITS}, f)
    return cb_tiny.run(root, "tiny.dry")


@pytest.mark.parametrize("name", DRY + PARTS)
def test_toy_cell_reports_the_metric_through_the_harness(result, name):
    assert result["correct"] is True
    m = result["metrics"][name]
    assert m["unit"] == ("%" if name in DRY else "ms")
    assert m["value"] >= 0.0


def test_toy_readings_hang_together(result):
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["host_dry_share.itl"] >= m["dry_chunk_fetch_share.itl"] \
        + m["dry_late_launch_share.itl"] - 1e-9
    assert m["host_dry_share.itl"] + m["no_work_share.itl"] <= 100.0
    assert m["dry_chunk_fetch_share.itl"] > 0.0   # a drain a request
    assert all(m[p] > 0.0 for p in PARTS)
    assert max(m[p] for p in PARTS) <= m["decode_launch_ms_p50.itl"]
    # no trace, no device idle time to explain: the metric is left out
    assert "idle_explained_share.itl" not in m


# -- program_dry on synthetic records ----------------------------------------

WINDOW = {"window": {"t_open": 10.0, "t_close": 20.0}}


def _rec(t, dry=(), **kw):
    rec = dict.fromkeys(["dry_" + c for c in CAUSES], 0.0)
    rec.update(t_start=t, wall=0.01, decode_launch=0.004, dry=list(dry), **kw)
    for t0, t1, cause in dry:
        rec["dry_" + cause] += t1 - t0
    return rec


def _serve(monkeypatch, recs):
    from incubator_mxnet_tpu.telemetry import tracing

    monkeypatch.setattr(
        tracing, "step_records", lambda since=None, until=None: [
            r for r in recs if since <= r["t_start"] < until])


RECS = [_rec(9.0, [[8.0, 9.0, "no_work"]]),                  # before the open
        _rec(10.5, [[9.5, 10.5, "no_work"]]),                # charged whole
        _rec(12.0, [[11.990, 11.995, "chunk_fetch"],
                    [11.998, 11.999, "late_launch"]]),
        _rec(13.0),
        _rec(14.0, [[13.9, 13.92, "cold_fetch"]]),
        _rec(15.0, [[14.995, 14.998, "chunk_fetch"]]),
        _rec(20.0, [[19.0, 20.0, "no_work"]])]               # at the close


def test_dry_reader_sums_a_cause_over_the_window(monkeypatch):
    _serve(monkeypatch, RECS)
    read = program_dry.read
    assert read(WINDOW, "chunk_fetch") == pytest.approx(0.08)    # 8 ms of 10 s
    assert read(WINDOW, "late_launch") == pytest.approx(0.01)
    assert read(WINDOW, "no_work") == pytest.approx(10.0)
    assert read(WINDOW, ["cold_fetch"]) == pytest.approx(0.2)


def test_host_dry_share_equals_the_sum_of_its_three_causes(monkeypatch):
    _serve(monkeypatch, RECS)
    read = program_dry.read
    three = ["chunk_fetch", "cold_fetch", "late_launch"]
    assert read(WINDOW, three) == pytest.approx(
        sum(read(WINDOW, c) for c in three))
    assert read(WINDOW, three) == pytest.approx(0.29)


def test_dry_reader_reads_nothing_where_there_is_nothing(monkeypatch):
    _serve(monkeypatch, RECS)
    assert program_dry.read({"window": {"t_open": 30.0, "t_close": 40.0}},
                            "no_work") is None
    # the parent's records have no such field: left out, never raised
    _serve(monkeypatch, [{"t_start": 12.0, "wall": 0.01,
                          "decode_launch": 0.004}])
    assert program_dry.read(WINDOW, "no_work") is None
    assert program_dry.read(WINDOW, ["chunk_fetch", "late_launch"]) is None


def test_dry_reader_reads_nothing_from_a_program_without_records(monkeypatch):
    from incubator_mxnet_tpu.telemetry import tracing

    monkeypatch.delattr(tracing, "step_records")
    assert program_dry.read(WINDOW, "no_work") is None
    assert idle_explained.dry_intervals(
        dict(WINDOW, trace=None, trace_clock=(10.0, 16.0))) is None


def test_launch_part_is_a_field_the_steps_reader_takes_a_percentile_of(
        monkeypatch):
    recs = [_rec(11.0, launch_key=0.0004), _rec(12.0, launch_key=0.0006),
            _rec(13.0, launch_key=0.0), _rec(14.0, launch_key=0.0011)]
    _serve(monkeypatch, recs)
    spec = harness.Spec("gpt2xl.chat")
    params = spec.metric("launch_key_ms_p50.itl")["params"]
    assert program_steps.read(WINDOW, **params) == pytest.approx(0.6)


# -- idle_explained on synthetic lanes ---------------------------------------

MS = 1_000_000
LO = 5 * MS                     # the trace's clock: nanoseconds
CLOCK = (100.0, 100.010)        # the same 10 ms on perf_counter


def _obs(monkeypatch, dry, busy=((0, 2), (4, 5), (8, 10)), clock=CLOCK):
    """10 ms of trace, busy over `busy` (ms from the window's start): the
    device is idle over 2-4 and 5-8 ms."""
    lanes = {"devices": {"/device:TPU:0": {trace.OPS: [
        [f"fusion.{i}", LO + a * MS, (b - a) * MS]
        for i, (a, b) in enumerate(busy)]}},
        "host": [[trace.WINDOW_SPAN, LO, 10 * MS]]}
    recs = [_rec(100.0 + 0.001 * i, [iv]) for i, iv in enumerate(dry)] \
        or [_rec(100.0)]
    _serve(monkeypatch, recs)
    return {"window": {"t_open": 99.0, "t_close": 145.0},
            "trace": trace.Trace(lanes), "trace_clock": clock}


def test_anchor_maps_the_window_ends_onto_the_traces(monkeypatch):
    obs = _obs(monkeypatch, [])
    ns = idle_explained.to_trace_ns(obs["trace_clock"], obs["trace"])
    assert ns(100.0) == pytest.approx(LO) and ns(100.010) == pytest.approx(
        LO + 10 * MS)
    assert ns(100.0025) == pytest.approx(LO + 2.5 * MS)
    # a host clock that ran 1 % fast against the trace's is scaled, not cut
    ns = idle_explained.to_trace_ns((100.0, 100.0101), obs["trace"])
    assert ns(100.0101) == pytest.approx(LO + 10 * MS)


@pytest.mark.parametrize("dry, explained_ms", [
    ([], 0.0),
    # a gap wholly inside a dry interval (which also covers busy time)
    ([[100.0015, 100.0045, "chunk_fetch"]], 2.0),
    # an interval wholly inside a gap
    ([[100.0055, 100.0065, "late_launch"]], 1.0),
    # an interval that straddles a gap's end: only the idle part counts
    ([[100.0070, 100.0090, "cold_fetch"]], 1.0),
    # wholly outside any gap
    ([[100.0005, 100.0015, "late_launch"]], 0.0),
    # overlapping intervals of two causes are counted once
    ([[100.0020, 100.0035, "chunk_fetch"], [100.0030, 100.0040, "no_work"]],
     2.0),
    # everything: both gaps
    ([[99.0, 101.0, "no_work"]], 5.0)])
def test_idle_time_inside_a_dry_interval_is_explained(monkeypatch, dry,
                                                      explained_ms):
    obs = _obs(monkeypatch, dry)
    assert idle_explained.read(obs) == pytest.approx(
        100.0 * explained_ms / 5.0, abs=1e-6)


def test_an_interval_beyond_the_traced_stretch_is_cut_to_it(monkeypatch):
    obs = _obs(monkeypatch, [[99.9, 100.0030, "no_work"],
                             [100.0075, 100.5, "no_work"]])
    dry = idle_explained.dry_intervals(obs)
    assert dry[0][0] == LO and dry[-1][1] == LO + 10 * MS
    assert idle_explained.read(obs) == pytest.approx(100.0 * 1.5 / 5.0)


def test_idle_explained_reads_nothing_without_its_inputs(monkeypatch):
    obs = _obs(monkeypatch, [[100.0015, 100.0045, "chunk_fetch"]])
    assert idle_explained.read(dict(obs, trace=None)) is None
    assert idle_explained.read(dict(obs, trace_clock=None)) is None
    busy = _obs(monkeypatch, [[100.001, 100.002, "late_launch"]],
                busy=((0, 10),))
    assert idle_explained.read(busy) is None          # never idle
    # the parent's records keep no intervals
    _serve(monkeypatch, [{"t_start": 100.0, "wall": 0.01}])
    assert idle_explained.read(obs) is None
