"""The harness end to end on the CPU, with the look for a chip skipped: toy
cells added as files in a temporary directory run and prove correct; with the
timed path broken underneath, `correct` comes out false — once for each fault
a cell can have."""
import json

import numpy as onp
import pytest

import cb_tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cb_tiny.make_root(tmp_path_factory.mktemp("cb"))


def last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_added_serving_cells_run_and_are_correct(root, capsys):
    """A cell, a configuration, a traffic mix and a metric with a reader of
    its own, each only a new file: nothing under chipbench/ was edited."""
    res = cb_tiny.run(root, "tiny.chat")
    line = last_json(capsys)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p50_ms", "setup_s", "serve_tokens_s",
                                   "toy_requests_s"}
    assert res["notes"]["in_window"]["compiles"] == 0
    assert res["metrics"]["toy_requests_s"]["unit"] == "requests/s"
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert line["compared"]["logit_gap_max"]["limit"] == \
        cb_tiny.SERVE_LIMITS["logit_gap_max"]


def test_altered_token_is_not_correct(root, monkeypatch):
    """A token altered where it is produced: the decode program's output."""
    from incubator_mxnet_tpu.serve.engine import SlotDecoder

    inner = SlotDecoder.decode_step

    def altered(self, *a, **kw):
        return (inner(self, *a, **kw) + 1) % 500

    monkeypatch.setattr(SlotDecoder, "decode_step", altered)
    res = cb_tiny.run(root, "tiny.chat")
    assert res["correct"] is False


def test_training_cell_is_correct_and_rate_is_not_quantised(root):
    res = cb_tiny.run(root, "tiny.train")
    assert res["correct"] is True
    steps, wall = res["attempted"], res["notes"]["wall_s"]
    assert wall >= 1.0                      # the time that really passed
    assert res["metrics"]["train_tokens_s"]["value"] == \
        pytest.approx(steps * 4 * 32 / wall)


def test_step_that_leaves_state_unchanged_is_not_correct(root, monkeypatch):
    from incubator_mxnet_tpu import optimizer

    monkeypatch.setattr(optimizer.Adam, "step",
                        lambda self, w, g, state, lr, wd, t: (w, state))
    res = cb_tiny.run(root, "tiny.train")
    assert res["correct"] is False


def test_half_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    from incubator_mxnet_tpu.parallel.sharded import DataParallel

    inner = DataParallel.step

    def half(self, x, y):
        n = x.shape[0] // 2
        return inner(self, x[:n], y[:n])

    monkeypatch.setattr(DataParallel, "step", half)
    res = cb_tiny.run(root, "tiny.train")
    assert res["correct"] is False


def test_mesh_cell_is_correct_and_a_left_out_exchange_is_not(root,
                                                             monkeypatch):
    """dp=4 on four virtual CPU devices; then every chip but the first is
    given nothing new to add (its rows repeat the first chip's), which is
    what a step without the gradient exchange computes on chip 0."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    res = cb_tiny.run(root, "tiny.dp4")
    assert res["correct"] is True

    from incubator_mxnet_tpu import np
    from incubator_mxnet_tpu.parallel.sharded import DataParallel

    inner = DataParallel.step

    def no_exchange(self, x, y):
        n = x.shape[0] // 4
        xs, ys = onp.asarray(x.asnumpy()), onp.asarray(y.asnumpy())
        return inner(self, np.array(onp.tile(xs[:n], (4, 1))),
                     np.array(onp.tile(ys[:n], (4, 1))))

    monkeypatch.setattr(DataParallel, "step", no_exchange)
    res = cb_tiny.run(root, "tiny.dp4")
    assert res["correct"] is False


def control_lines(root, capsys, cell, seeds):
    """`control.py` at a toy size, the look for a chip skipped."""
    from chipbench import control

    capsys.readouterr()
    control.main(["--workload", cell, "--seeds", seeds, "--control-seeds", "3",
                  "--seconds", "1.5", "--root", root, "--any-device"])
    out = [json.loads(ln[8:]) for ln in capsys.readouterr().out.splitlines()
           if ln.startswith("CONTROL ")]
    by_side = {}
    for line in out:
        by_side.setdefault(line["side"], []).append(line)
    return by_side


def test_training_control_comes_out_not_correct(root, capsys):
    """The reference in the program's place with int8 matmul inputs, and with
    half of the batch left out, comes out not correct on every seed by the
    harness's own comparison at the cell's limits; the program is correct."""
    got = control_lines(root, capsys, "tiny.train", "21,22,23")
    assert [line["correct"] for line in got["program"]] == [True] * 3
    for side in ("control_int8", "fault_half_batch"):
        assert [line["correct"] for line in got[side]] == [False] * 3, side
        assert all(line["failed"] for line in got[side])


def test_serving_control_comes_out_not_correct(root, capsys):
    """int8 in the reference's place: at each position the token it puts
    first lies further below the float32 reference's best than the cell's
    limits allow; the program's own served tokens do not."""
    got = control_lines(root, capsys, "tiny.chat", "33,34,35")
    assert [line["correct"] for line in got["program"]] == [True] * 3
    assert [line["correct"] for line in got["control_int8"]] == [False] * 3
    assert all({"logit_gap_max", "logit_gap_mean"} & set(line["failed"])
               for line in got["control_int8"])


def test_a_control_is_judged_by_the_harness_at_the_cells_limits():
    """The control's lines go through `harness.passed` with the limits of
    the cell's own file, as a run's do."""
    from chipbench.lib import harness
    from chipbench.runners import serve

    limits = {"logit_gap_max": 0.1, "logit_gap_mean": 0.002}
    low = serve.gap_checks(onp.asarray([0.0] * 99 + [0.05]), limits)
    high = serve.gap_checks(onp.asarray([0.0] * 9 + [0.2]), limits)
    assert harness.all_passed(low) and not harness.all_passed(high)
    assert [c["name"] for c in high if not harness.passed(c)] == \
        ["logit_gap_max", "logit_gap_mean"]
    assert [c["limit"] for c in high] == [0.1, 0.002]
