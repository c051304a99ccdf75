"""Each plain reference against the program's Gluon block at a tiny size on
the CPU, on the seeded weights both get from `chipbench.lib.seeded`; and the
control readings at that size."""
import numpy as onp
import pytest

import cb_tiny
from chipbench.lib import seeded
from chipbench.reference import bert as ref_bert
from chipbench.reference import gpt as ref_gpt
from chipbench.runners import train

GPT = cb_tiny.FILES["configs/gpt-tiny.json"]
BERT = cb_tiny.FILES["configs/bert-tiny.json"]


def test_leaves_name_every_parameter_once():
    for ref, cfg, want in ((ref_gpt, GPT, 2 * 12 + 4), (ref_bert, BERT, 2 * 12 + 13)):
        names = [n for n, *_ in ref.leaves(cfg)]
        assert len(names) == len(set(names)) == want
    xl = {"n_layer": 48, "n_embd": 1600, "n_head": 25, "n_inner": None,
          "vocab_size": 50257, "n_positions": 1024}
    assert ref_gpt.n_params(xl) == 1557611200     # GPT-2 XL: 1.56 B


def test_seeded_values_repeat_and_depend_on_seed_tag_and_layer():
    leaves = ref_gpt.leaves(GPT)
    a, b = seeded.values(leaves, 2**31 + 5), seeded.values(leaves, 2**31 + 5)
    assert all(onp.array_equal(a[k], b[k]) for k in a)
    c = seeded.values(leaves, 5)
    assert not onp.array_equal(a["blocks.0.ln1.beta"], c["blocks.0.ln1.beta"])
    assert not onp.array_equal(a["blocks.0.ln1.beta"], a["blocks.1.ln1.beta"])
    assert not onp.array_equal(a["blocks.0.ln1.beta"], a["blocks.0.ln2.beta"])
    assert abs(float(a["blocks.0.ln1.gamma"].mean()) - 1) < 0.02


def test_gpt_reference_matches_the_gluon_forward():
    from incubator_mxnet_tpu import np
    from incubator_mxnet_tpu.models import gpt

    n_layer, c, n_head, f, v, n_pos = ref_gpt.sizes(GPT)
    net = gpt.GPTModel(v, c, f, n_layer, n_head, n_pos, dropout=0.0)
    seeded.fill(net, ref_gpt.leaves(GPT), 11)
    tokens = onp.random.default_rng(0).integers(0, v, (2, 24)).astype("int32")
    want = net(np.array(tokens)).asnumpy()
    rows = [(b, t) for b in range(2) for t in range(24)]
    got = ref_gpt.logits_at(GPT, 11, tokens, rows).reshape(2, 24, v)
    assert onp.abs(got - want).max() < 2e-4 * onp.abs(want).max()
    low = ref_gpt.logits_at(GPT, 11, tokens, rows, "int8").reshape(2, 24, v)
    assert onp.abs(low - want).max() > 20 * onp.abs(got - want).max()


def test_bert_reference_matches_the_gluon_loss():
    from incubator_mxnet_tpu import gluon, np
    from incubator_mxnet_tpu.models import bert

    n_layer, c, n_head, f, v, n_pos, _ = ref_bert.sizes(BERT)
    net = bert.BERTModel(v, c, f, n_layer, n_head, n_pos, dropout=0.0)
    seeded.fill(net, ref_bert.leaves(BERT), 12)
    x, y = train.batch_of(12, 1, 4, 32, v)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    want = float(ce(net(np.array(x))[0], np.array(y)).mean().asnumpy())
    params = seeded.values(ref_bert.leaves(BERT), 12)
    got, grads = ref_bert.loss_and_grads(BERT, params, x, y, block_rows=2)
    assert float(got) == pytest.approx(want, rel=2e-5)
    # the segment table and the next-sentence head take no part
    for name in ("encoder.token_type_embed.weight", "nsp.weight", "nsp.bias"):
        assert float(onp.abs(grads[name]).max()) == 0.0
    whole, _ = ref_bert.loss_and_grads(BERT, params, x, y, block_rows=4)
    assert float(whole) == pytest.approx(float(got), rel=1e-5)


def test_reference_adam_is_mxnets():
    """One step by hand on a one-leaf 'model': w - lr_t m / (sqrt(v) + eps)."""
    import jax.numpy as jnp

    w, g = jnp.asarray([1.0, -2.0]), jnp.asarray([0.5, -0.25])
    zero = jnp.zeros(2)
    new, m, v = ref_bert._adam_program()(
        {"w": w}, {"w": g}, {"w": zero}, {"w": jnp.zeros(2)}, jnp.float32(1),
        jnp.float32(1e-2), jnp.float32(0.9), jnp.float32(0.999),
        jnp.float32(1e-8))
    m1, v1 = 0.1 * onp.asarray(g), 0.001 * onp.asarray(g) ** 2
    lr_t = 1e-2 * onp.sqrt(1 - 0.999) / (1 - 0.9)
    want = onp.asarray([1.0, -2.0]) - lr_t * m1 / (onp.sqrt(v1) + 1e-8)
    assert onp.allclose(new["w"], want, rtol=1e-5)
    assert onp.allclose(m["w"], m1) and onp.allclose(v["w"], v1, rtol=1e-5)


def test_compare_measures_norm_gaps_by_the_worst_leaf():
    names = ["a", "b", "dead"]
    vec = lambda *v: onp.asarray(v, onp.float64)  # noqa: E731
    ref = {"loss": [10.0, 9.0, 8.0],
           "grad": {"a": vec(2.0, 0.0), "b": vec(0.0, 0.02), "dead": vec(0.0, 0.0)},
           "grad_norm": {"a": 2.0, "b": 0.02, "dead": 0.0},
           "delta_norm": {"a": 1.0, "b": 1.0, "dead": 0.0}}
    prog = {"loss": [10.1, 9.0, 8.0],
            "grad": [vec(2.2, 0.0), vec(0.0, 0.03), vec(0.0, 0.0)],
            "grad_norm": onp.asarray([2.2, 0.03, 0.0]),
            "delta_norm": onp.asarray([1.0, 0.9, 0.5])}
    got = train.compare(prog, ref, names)
    assert got["loss_gap_step1"] == pytest.approx(0.01)
    # a: 0.2 / 2.0; b: 0.01 / max(0.02, median 0.02)
    assert got["grad_norm_gap_worst_leaf"] == pytest.approx(0.5)
    # the difference as a vector: by the worst leaf as above, and over all
    # leaves sqrt(0.2^2 + 0.01^2) / sqrt(2^2 + 0.02^2)
    assert got["grad_diff_worst_leaf"] == pytest.approx(0.5)
    assert got["grad_diff_all_leaves"] == pytest.approx(
        (0.2 ** 2 + 0.01 ** 2) ** 0.5 / (2.0 ** 2 + 0.02 ** 2) ** 0.5)
    # "dead" has no gradient in the reference: its change is left out
    assert got["delta_norm_gap_worst_leaf"] == pytest.approx(0.1)
    assert got["_worst"] == {"grad": "b", "grad_diff": "b", "delta": "b"}
