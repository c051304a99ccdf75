"""Decoder-only causal LM (`models/gpt.py`): causality, training step,
hybridize, generation (reference role: GluonNLP GPT-2)."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, np, optimizer
from incubator_mxnet_tpu.models.gpt import gpt_tiny
from incubator_mxnet_tpu.parallel.sharded import DataParallel


@pytest.fixture(scope="module")
def net():
    mx.random.seed(0)
    m = gpt_tiny(vocab_size=97, max_length=32, dropout=0.0)
    m.initialize()
    return m


def _tok(batch, t, seed=0, vocab=97):
    r = onp.random.RandomState(seed)
    return np.array(r.randint(0, vocab, (batch, t)).astype("int32"))


def test_forward_shape_and_determinism(net):
    x = _tok(2, 16)
    out = net(x)
    assert out.shape == (2, 16, 97)
    onp.testing.assert_allclose(out.asnumpy(), net(x).asnumpy(), rtol=1e-6)


def test_causality(net):
    """Changing a future token must not change past logits."""
    x1 = _tok(1, 16, seed=1)
    x2_np = x1.asnumpy().copy()
    x2_np[0, 10:] = (x2_np[0, 10:] + 1) % 97     # perturb tokens >= 10
    out1 = net(x1).asnumpy()
    out2 = net(np.array(x2_np.astype("int32"))).asnumpy()
    onp.testing.assert_allclose(out1[0, :10], out2[0, :10],
                                rtol=1e-5, atol=1e-5)
    assert not onp.allclose(out1[0, 10:], out2[0, 10:])


def test_train_step_reduces_loss(net):
    """Next-token LM training on a repeating pattern: loss must drop."""
    mx.random.seed(3)
    m = gpt_tiny(vocab_size=17, max_length=32, dropout=0.0)
    m.initialize()
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def lm_loss(logits, y):
        return ce(logits.reshape(-1, 17), y.reshape(-1))

    dp = DataParallel(m, lm_loss, optimizer.Adam(learning_rate=3e-3))
    seq = onp.tile(onp.arange(16), 3)[:32].astype("int32")  # periodic
    x = np.array(onp.stack([seq[:-1]] * 4))
    y = np.array(onp.stack([seq[1:]] * 4))
    first = float(dp.step(x, y).asnumpy())
    for _ in range(30):
        last = float(dp.step(x, y).asnumpy())
    assert last < first * 0.5, (first, last)


def test_hybridize_matches_eager(net):
    x = _tok(2, 12, seed=5)
    ref = net(x).asnumpy()
    net.hybridize()
    out1 = net(x).asnumpy()   # eager probe
    out2 = net(x).asnumpy()   # compiled
    onp.testing.assert_allclose(out1, ref, rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(out2, ref, rtol=1e-4, atol=1e-5)
    net.hybridize(False)


def test_generate_greedy_extends(net):
    x = _tok(2, 4, seed=7)
    out = net.generate(x, max_new_tokens=5)
    assert out.shape == (2, 9)
    onp.testing.assert_array_equal(out.asnumpy()[:, :4], x.asnumpy())
    # greedy decode is deterministic
    out2 = net.generate(x, max_new_tokens=5)
    onp.testing.assert_array_equal(out.asnumpy(), out2.asnumpy())
    # top-k restricted sampling stays in vocab
    out3 = net.generate(x, max_new_tokens=3, top_k=5, do_sample=True)
    assert int(out3.asnumpy().max()) < 97


def _fresh_spicy():
    """Random-weight net with non-degenerate logits (scaled init breaks
    the argmax collapse of a freshly initialized model, so greedy parity
    actually exercises token-dependent paths)."""
    mx.random.seed(11)
    m = gpt_tiny(vocab_size=97, max_length=64, dropout=0.0)
    m.initialize()
    r = onp.random.RandomState(42)
    for _name, p in m.collect_params().items():
        if p.shape and len(p.shape) >= 2:
            p.set_data(np.array(
                r.normal(0, 0.35, p.shape).astype("float32")))
    return m


@pytest.fixture(scope="module")
def spicy_net():
    return _fresh_spicy()


def test_kv_cache_greedy_matches_full_forward(spicy_net):
    """The compiled KV-cache decode (one XLA program, static cache) must
    emit exactly the tokens of the eager O(T²) full-forward loop."""
    for seed, (b, t0, tnew) in [(0, (2, 12, 20)), (1, (1, 1, 8)),
                                (2, (3, 7, 1))]:
        x = _tok(b, t0, seed=seed)
        ref = spicy_net.generate(x, tnew, use_cache=False).asnumpy()
        got = spicy_net.generate(x, tnew, use_cache=True).asnumpy()
        assert got.shape == (b, t0 + tnew)
        onp.testing.assert_array_equal(ref, got)


def test_kv_cache_sampling_seeded_and_varied(spicy_net):
    x = _tok(2, 8, seed=3)
    a = spicy_net.generate(x, 12, do_sample=True, top_k=8,
                           temperature=0.9, seed=5).asnumpy()
    b = spicy_net.generate(x, 12, do_sample=True, top_k=8,
                           temperature=0.9, seed=5).asnumpy()
    c = spicy_net.generate(x, 12, do_sample=True, top_k=8,
                           temperature=0.9, seed=6).asnumpy()
    onp.testing.assert_array_equal(a, b)         # seeded => reproducible
    assert not (a == c).all()                     # seed changes the draw
    # all sampled tokens inside the vocab
    assert int(a.max()) < 97 and int(a.min()) >= 0
    # temperature~0 sampling collapses to greedy
    g = spicy_net.generate(x, 12, use_cache=True).asnumpy()
    t0 = spicy_net.generate(x, 12, do_sample=True, temperature=1e-6,
                            seed=5).asnumpy()
    onp.testing.assert_array_equal(g, t0)


def test_kv_cache_respects_max_length(spicy_net):
    x = _tok(1, 60, seed=4)
    with pytest.raises(ValueError):
        spicy_net.generate(x, 8, use_cache=True)   # 68 > max_length 64


def test_bucket_prompt_helper():
    """bucket_prompt pads to the smallest fitting bucket, accounts the
    waste, and passes through prompts beyond every bucket."""
    from incubator_mxnet_tpu.models.decoding import bucket_prompt
    from incubator_mxnet_tpu.telemetry import registry

    ctr = registry.counter(
        "mx_decode_bucket_pad_tokens_total",
        "prompt tokens added by pad-to-bucket in the decode/serving "
        "path (padding waste)")
    before = ctr.value
    ids = onp.arange(10, dtype=onp.int32).reshape(2, 5)
    padded, t0 = bucket_prompt(ids, buckets=(8, 16))
    assert padded.shape == (2, 8) and t0 == 5
    onp.testing.assert_array_equal(onp.asarray(padded)[:, :5], ids)
    assert ctr.value == before + 2 * 3      # 2 rows x 3 pad tokens
    # exact-bucket and beyond-every-bucket prompts pass through unpadded
    p8, t8 = bucket_prompt(onp.zeros((1, 8), onp.int32), buckets=(8, 16))
    assert p8.shape == (1, 8) and t8 == 8
    p20, t20 = bucket_prompt(onp.zeros((1, 20), onp.int32), buckets=(8, 16))
    assert p20.shape == (1, 20) and t20 == 20
    # max_len caps the candidate buckets
    p5, _ = bucket_prompt(onp.zeros((1, 5), onp.int32), buckets=(8, 16),
                          max_len=8)
    assert p5.shape == (1, 8)
    with pytest.raises(ValueError):
        bucket_prompt(onp.zeros((5,), onp.int32))


def test_generate_buckets_share_one_program(spicy_net):
    """Ad-hoc prompt lengths inside one bucket must NOT compile one XLA
    program each — the pre-bucketing behavior this satellite kills."""
    from incubator_mxnet_tpu.models.decoding import GPTDecoder

    dec = GPTDecoder(spicy_net)
    for t0 in (3, 7, 11, 18):              # all land in the 32 bucket
        dec.generate(_tok(1, t0, seed=t0), 4)
    size = getattr(dec._generate_fn, "_cache_size", None)
    if size is not None:                   # jax-version-dependent probe
        assert size() == 1, "one bucket must mean one compiled program"


def test_decoder_auto_refresh_without_explicit_refresh(spicy_net, caplog):
    """Forgetting refresh() after a parameter update must no longer
    produce stale logits: the decoder fingerprints the source Block's
    parameter buffers and auto-refreshes (warning once)."""
    import logging

    from incubator_mxnet_tpu.models.decoding import GPTDecoder

    dec = GPTDecoder(spicy_net)
    x = _tok(1, 6, seed=21)
    before = dec.generate(x, 8).asnumpy()
    p = spicy_net.word_embed.weight
    old = p.data().asnumpy()
    try:
        r = onp.random.RandomState(321)
        p.set_data(np.array(r.normal(0, 0.35, p.shape).astype("float32")))
        with caplog.at_level(logging.WARNING,
                             logger="incubator_mxnet_tpu.models"):
            after = dec.generate(x, 8).asnumpy()   # NO refresh() call
        assert any("auto-refreshing" in m for m in caplog.messages)
        ref = spicy_net.generate(x, 8, use_cache=False).asnumpy()
        onp.testing.assert_array_equal(after, ref)
        assert not (before == after).all()
        # the warning fires once, not per call
        caplog.clear()
        with caplog.at_level(logging.WARNING,
                             logger="incubator_mxnet_tpu.models"):
            p.set_data(np.array(old))
            dec.generate(x, 8)
        assert not any("auto-refreshing" in m for m in caplog.messages)
    finally:
        p.set_data(np.array(old))


def test_kv_cache_sees_updated_params(spicy_net):
    """generate() after a parameter change must reflect the new weights
    (the decoder re-reads parameters per call)."""
    x = _tok(1, 6, seed=9)
    before = spicy_net.generate(x, 8).asnumpy()
    p = spicy_net.word_embed.weight
    old = p.data().asnumpy()
    try:
        r = onp.random.RandomState(123)
        p.set_data(np.array(r.normal(0, 0.35, p.shape).astype("float32")))
        after = spicy_net.generate(x, 8).asnumpy()
        ref = spicy_net.generate(x, 8, use_cache=False).asnumpy()
        onp.testing.assert_array_equal(after, ref)
        assert not (before == after).all()
    finally:
        p.set_data(np.array(old))


def test_decoder_holds_each_layer_in_the_form_the_matmuls_read(spicy_net):
    """The stored form (PR 32): a tuple of one dict a layer, every leaf
    float32 as the block holds it (no precision traded), each matrix the
    transposed copy ``(in, out)`` of the block's ``(out, in)``, and the two
    tables with their rows padded to whole lanes."""
    import jax

    from incubator_mxnet_tpu.models.decoding import GPTDecoder

    p = GPTDecoder(spicy_net)._params
    assert all(leaf.dtype == "float32" for leaf in jax.tree.leaves(p))
    assert isinstance(p["layers"], tuple)
    assert len(p["layers"]) == len(spicy_net.blocks) == 2
    C, F, V = 64, 128, 97
    shapes = {"qkv_w": (C, 3 * C), "proj_w": (C, C), "ffn1_w": (C, F),
              "ffn2_w": (F, C), "qkv_b": (3 * C,), "proj_b": (C,),
              "ffn1_b": (F,), "ffn2_b": (C,), "ln1_g": (C,), "ln1_b": (C,),
              "ln2_g": (C,), "ln2_b": (C,)}
    for blk, lp in zip(spicy_net.blocks, p["layers"]):
        assert {n: a.shape for n, a in lp.items()} == shapes
        for name, w in (("qkv_w", blk.attn.qkv.weight),
                        ("proj_w", blk.attn.proj.weight),
                        ("ffn1_w", blk.ffn.ffn1.weight),
                        ("ffn2_w", blk.ffn.ffn2.weight)):
            onp.testing.assert_array_equal(onp.asarray(lp[name]),
                                           w.data().asnumpy().T)
        onp.testing.assert_array_equal(onp.asarray(lp["qkv_b"]),
                                       blk.attn.qkv.bias.data().asnumpy())
    # the tied embedding is ONE table: rows gathered from it, the logits
    # contracted over its last axis; no second leaf
    embed = spicy_net.word_embed.weight.data().asnumpy()
    assert set(p) == {"layers", "embed", "pos", "lnf_g", "lnf_b"}
    assert p["embed"].shape == (V, 128) and p["pos"].shape == (64, 128)
    onp.testing.assert_array_equal(onp.asarray(p["embed"])[:, :C], embed)
    assert not onp.asarray(p["embed"])[:, C:].any()
    assert not onp.asarray(p["pos"])[:, C:].any()


def test_decoder_owns_its_layers_the_blocks_arrays_may_be_deleted():
    """`chipbench/runners/serve.py` frees every ``blocks.*`` array of the
    Gluon block once the engine is built (two float32 copies of GPT-2 XL and
    the KV pool do not fit one chip): every per-layer leaf, the biases and
    the LayerNorm gains too, has to be a buffer of the decoder's own."""
    from incubator_mxnet_tpu.models.decoding import GPTDecoder

    net = _fresh_spicy()
    x = _tok(2, 9, seed=4)
    dec = GPTDecoder(net)
    want = dec.generate(x, 7).asnumpy()
    n = 0
    for name, p in net.collect_params().items():
        if name.startswith("blocks."):
            p.data()._data.delete()
            n += 1
    assert n == 2 * 12
    onp.testing.assert_array_equal(dec.generate(x, 7).asnumpy(), want)


def test_untied_head_is_a_table_of_its_own():
    """``tie_weights=False``: the logits contract over ``head``, a table
    stored like `embed` (``lm_head.weight`` ``(V, C)``, rows padded to
    whole lanes); cached greedy decode gives the full forward's tokens."""
    from incubator_mxnet_tpu.models.decoding import GPTDecoder
    from incubator_mxnet_tpu.models.gpt import GPTModel

    mx.random.seed(5)
    net = GPTModel(97, 64, 128, 2, 4, 64, dropout=0.0, tie_weights=False)
    net.initialize()
    r = onp.random.RandomState(7)
    for _name, p in net.collect_params().items():
        if p.shape and len(p.shape) >= 2:
            p.set_data(np.array(
                r.normal(0, 0.35, p.shape).astype("float32")))
    p = GPTDecoder(net)._params
    assert p["head"].shape == (97, 128)
    onp.testing.assert_array_equal(onp.asarray(p["head"])[:, :64],
                                   net.lm_head.weight.data().asnumpy())
    x = _tok(2, 7, seed=3)
    onp.testing.assert_array_equal(
        net.generate(x, 6).asnumpy(),
        net.generate(x, 6, use_cache=False).asnumpy())
