"""chip_smoke.py refuses to pass anywhere but on a TPU, and the compile
cache is placed from outside or at one fixed path — checked in-process on
the CPU (the chip run itself is `python chip_smoke.py` through the builder's
chip tool)."""
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from incubator_mxnet_tpu._startup import configure_compile_cache  # noqa: E402


def test_smoke_refuses_a_platform_that_is_not_tpu(capsys):
    # --tiny: were the refusal ever lost, this test would run toy phases
    # and fail on the "ok" line instead of training BERT-base on a CPU
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(["--tiny"])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    # nothing was set in code: jax keeps what it read for itself
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_fixed_directory(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert configure_compile_cache() == fixed
    assert configure_compile_cache() == fixed      # no pid, time or temp name
    assert jax.config.jax_compilation_cache_dir == fixed


def test_eva_phase_rehearsal_crosses_a_roll_and_agrees_with_the_reference(capsys):
    """The EvaByte phase at its toy size on the CPU: one roll in prefill,
    one in decode, tokens the reference's."""
    chip_smoke.eva_phase(chip_smoke.EVA_SIZES[True], 3)
    out = capsys.readouterr().out
    assert "across 2 rolls" in out
    # the step records' account of the decode launches (ISSUE 30)
    line = next(ln for ln in out.splitlines() if "eva: " in ln
                and "decode launches" in ln)
    assert "overshoot rows 0" in line and "(0.0 %)" not in line


def test_pangu_phase_rehearsal_agrees_with_the_reference_and_counts_pairs(capsys):
    """The openPangu-Ultra-MoE phase at its toy size on the CPU: chunked
    prefill, decode together, tokens the reference's, some pairs held."""
    chip_smoke.pangu_phase(chip_smoke.PANGU_SIZES[True], 3)
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if "pangu: " in ln
                and "held pairs" in ln)
    assert "26 tokens of 3 requests" in line
    assert "mla_decode_attention" in line and "moe_experts" in line


def test_serve_phase_rehearsal_says_how_the_decode_launches_were_made(capsys):
    """The GPT serve phase at its toy size on the CPU: the phases still
    cover the steps' wall with launch and fetch in different iterations,
    and the phase prints the share of launches made ahead of the last fetch
    and the overshoot count."""
    chip_smoke.serve_phase(chip_smoke.SERVE_SIZES[True], 0,
                           chip_smoke.CompileWatch())
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if "serve: " in ln
                and "decode launches" in ln)
    assert "made ahead of the last fetch" in line
    assert "overshoot rows 0" in line and "(0.0 %)" not in line


def test_launch_modes_refuses_a_run_with_no_launch_made_ahead():
    steps = [{"decoding": 2, "chunks": 0, "mode": "cold", "overshoot": 0}] * 3
    with pytest.raises(RuntimeError, match="0 decode launches made ahead"):
        chip_smoke.launch_modes(steps, "serve")
    steps = [{"decoding": 2, "chunks": 0, "mode": "ahead", "overshoot": 1}]
    with pytest.raises(RuntimeError, match="1 overshoot rows"):
        chip_smoke.launch_modes(steps, "serve")
