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
    assert "across 2 rolls" in capsys.readouterr().out
