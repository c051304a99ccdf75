"""Real-accelerator consistency gate (reference discipline:
`check_consistency` cpu-vs-gpu, test_utils.py:1491 / SURVEY §4).

The regular suite runs entirely on a virtual CPU mesh, so TPU-only
numerics (bf16 matmul defaults, pallas non-interpret kernels, int8 MXU
paths) are otherwise exercised only by the bench. This file compares a
core-op sample between the CPU backend and the REAL chip in one
process.

Run on a TPU host:  MX_TPU_TESTS=1 python -m pytest
tests/test_tpu_consistency.py -q     (conftest keeps the tpu platform
visible alongside cpu when MX_TPU_TESTS=1; without it, every test here
skips.)
"""
import os

import numpy as onp
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("MX_TPU_TESTS") != "1",
    reason="real-TPU consistency gate (set MX_TPU_TESTS=1 on a chip host)")


def _accel_device():
    import jax

    import incubator_mxnet_tpu as mx

    if not any(d.platform == "tpu" for d in jax.devices()):
        pytest.skip("no TPU visible")
    return mx.tpu(0)


def _pair(fn, inputs, rtol=2e-2, atol=5e-2):
    """check_consistency cpu-vs-accelerator. Tolerances follow the
    reference's fp16 row (test_utils.py:1491 uses rtol=1e-2, atol=1e-1
    for fp16-vs-fp32): TPU matmuls default to bf16 MXU accumulation, so
    near-zero entries of an O(N)-term contraction carry absolute error
    ~1e-2 that no rtol can absorb."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.test_utils import check_consistency

    check_consistency(fn, inputs, devices=[mx.cpu(0), _accel_device()],
                      rtol=rtol, atol=atol)


def _r(*shape, seed=0):
    from incubator_mxnet_tpu import np

    return np.array(onp.random.RandomState(seed)
                    .uniform(-1, 1, shape).astype("float32"))


def test_dot_consistency():
    from incubator_mxnet_tpu import np

    _pair(lambda a, b: np.dot(a, b), [_r(64, 64), _r(64, 64, seed=1)])


def test_conv_bn_relu_consistency():
    from incubator_mxnet_tpu import np, npx

    x = _r(2, 3, 16, 16)
    w = _r(8, 3, 3, 3, seed=1)
    gamma, beta = np.ones((8,)), np.zeros((8,))
    rm, rv = np.zeros((8,)), np.ones((8,))

    def f(x, w, gamma, beta, rm, rv):
        y = npx.convolution(x, w, kernel=(3, 3), num_filter=8,
                            pad=(1, 1), no_bias=True)
        return npx.relu(npx.batch_norm(y, gamma, beta, rm, rv))

    _pair(f, [x, w, gamma, beta, rm, rv])


def test_softmax_reduction_consistency():
    from incubator_mxnet_tpu import np, npx

    _pair(lambda x: npx.softmax(x, axis=-1).sum(axis=0), [_r(32, 128)])


def test_flash_attention_consistency():
    """pallas kernel on-chip vs the XLA fallback path on cpu."""
    from incubator_mxnet_tpu import npx

    q = _r(2, 4, 128, 64)
    k = _r(2, 4, 128, 64, seed=1)
    v = _r(2, 4, 128, 64, seed=2)
    _pair(lambda q, k, v: npx.flash_attention(q, k, v, causal=True),
          [q, k, v], rtol=3e-2, atol=3e-3)


def test_train_step_consistency():
    """One fwd+bwd+SGD step of a small MLP lands on the same weights."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon, np

    def step(x, y):
        onp.random.seed(0)
        mx.random.seed(0)      # same init draws on both devices
        net = gluon.nn.Sequential()
        net.add(gluon.nn.Dense(16, activation="relu"),
                gluon.nn.Dense(4))
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(x.shape[0])
        return [p.data() for p in net.collect_params().values()]

    x = _r(8, 12)
    y = mx.np.array(onp.random.RandomState(3)
                    .randint(0, 4, (8,)).astype("int32"))
    _pair(step, [x, y])


def test_fused_residual_ln_consistency():
    """ops/fused_block.py kernel on-chip vs the composed cpu path (p=0:
    the dropout mask is generator-specific, so the deterministic part of
    the contract is what cross-device consistency can pin)."""
    from incubator_mxnet_tpu import npx

    x = _r(2, 64, 256)
    h = _r(2, 64, 256, seed=1)
    g = _r(256, seed=2)
    b = _r(256, seed=3)
    _pair(lambda x, h, g, b: npx.residual_dropout_ln(x, h, g, b, p=0.0),
          [x, h, g, b], rtol=1e-2, atol=1e-2)


def test_fused_layer_norm_consistency():
    """ops/layer_norm.py kernel on-chip vs the XLA lowering on cpu."""
    from incubator_mxnet_tpu import npx

    x = _r(4, 32, 384)
    g = _r(384, seed=1)
    b = _r(384, seed=2)
    _pair(lambda x, g, b: npx.layer_norm(x, g, b), [x, g, b],
          rtol=1e-2, atol=1e-2)
