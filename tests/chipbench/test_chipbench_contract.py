"""BENCHMARK.json against the contract's limits and against the files under
chipbench/ that the harness reads; and the command's refusals."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import cb_tiny
from chipbench.lib import harness

BENCH = json.load(open(os.path.join(cb_tiny.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(kind, name):
    with open(os.path.join(harness.CHIPBENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cb_tiny.ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= len(BENCH["workloads"]) <= 24 and len(BENCH["per_layer"]) <= 128


@pytest.mark.parametrize("entry", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda e: e["name"])
def test_metric_entry_is_well_formed_and_matches_its_file(entry):
    e2e = entry in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(entry) <= allowed and allowed - {"workloads"} <= set(entry)
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in (SOURCES & {"host_clock", "device_trace"}
                               if e2e else SOURCES)
    f = load("metrics", entry["name"])
    for key in ("unit", "better", "source"):
        assert f[key] == entry[key]
    assert os.path.isfile(os.path.join(harness.CHIPBENCH, "readers",
                                       f["reader"] + ".py"))
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells
    if e2e:
        assert 0.01 <= entry["bound"] <= 0.1
    else:
        assert f["layer"] == entry["layer"] and "\t" not in entry["layer"]
        assert 1 <= len(entry["layer"]) <= 200
        moved = {e["name"]: e for e in BENCH["end_to_end"]}[entry["moves"]]
        reporting = set(moved.get("workloads", cells))
        assert set(entry["workloads"]) <= reporting
        if entry["unit"] == "%" and ("roofline" in entry["name"]
                                     or "mfu" in entry["name"]):
            assert entry["source"] == "device_trace"


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_entry_matches_the_files_the_harness_reads(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    spec = harness.Spec(cell["name"])
    for key in ("config", "traffic", "chips", "why"):
        assert spec.cell[key] == cell[key]
    reports = lambda group: {  # noqa: E731
        e["name"] for e in BENCH[group]
        if cell["name"] in e.get("workloads", [cell["name"]])}
    assert set(spec.cell["end_to_end"]) == reports("end_to_end")
    assert set(spec.cell["per_layer"]) == reports("per_layer")
    assert "setup_s" in spec.cell["end_to_end"] and len(spec.cell["end_to_end"]) >= 2
    assert spec.cell["per_layer"]
    for name in spec.cell["end_to_end"] + spec.cell["per_layer"]:
        spec.metric(name)
    assert spec.cell["limits"]
    configs = {c["name"]: c for c in BENCH["configs"]}
    entry = configs[cell["config"]]
    assert entry["file"] == f"chipbench/configs/{cell['config']}.json"
    assert spec.config["reduced"] == entry["reduced"]
    assert spec.config["source"] == entry["source"]
    widths = re.compile(r"(_dim|_rank)$|hidden_size|intermediate|n_embd|n_inner|head")
    assert not any(widths.search(k) for k in entry["reduced"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_an_open_loop_window_holds_exactly_one_cycle(cell):
    """`sizes` requests at `rate_rps` take `run_seconds`: every seed's window
    is due the same requests, and the ramp has a cycle of its own."""
    traffic = harness.Spec(cell["name"]).traffic
    if traffic["kind"] != "open_loop":
        pytest.skip("not an open loop")
    assert traffic["sizes"] / traffic["rate_rps"] == pytest.approx(
        BENCH["run_seconds"])
    assert traffic["ramp_sizes"] >= 1
    assert traffic["trace_s"] <= BENCH["run_seconds"]


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = [c for c in BENCH["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_every_file_under_paths_is_named_from_permitted_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in BENCH["paths"]:
        for d, _, files in os.walk(os.path.join(cb_tiny.ROOT, base)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), cb_tiny.ROOT))


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    spec = harness.Spec(BENCH["workloads"][0]["name"])
    assert spec.peak("TPU v5 lite") == {"flops_bf16": 197e12,
                                        "hbm_bytes_s": 819e9,
                                        "hbm_bytes": 17179869184}
    with pytest.raises(KeyError):
        spec.peak("TPU v9")


def run_command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu_with_no_result_line():
    cell = BENCH["workloads"][0]["name"]
    p = run_command(cb_tiny.ROOT, "--workload", cell, "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert p.returncode == 2 and "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_run_fails_where_only_the_benchmark_is_present(tmp_path):
    for base in BENCH["paths"]:
        shutil.copytree(os.path.join(cb_tiny.ROOT, base), tmp_path / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cb_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    p = run_command(tmp_path, "--workload", BENCH["workloads"][0]["name"],
                    "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_unknown_workload_is_refused():
    p = run_command(cb_tiny.ROOT, "--workload", "x", "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
