"""Rematerialization / memory-opt parity (`incubator_mxnet_tpu/remat.py`;
reference: MXNET_BACKWARD_DO_MIRROR + MXNET_MEMORY_OPT,
`docs/static_site/src/pages/api/faq/env_var.md:230-238`, nnvm mirror pass
`src/nnvm/gradient.cc`).

Memory is asserted on the autodiff RESIDUAL ledger
(`jax.ad_checkpoint.saved_residuals` — the forward→backward live set that
remat governs): final HBM peaks belong to XLA's buffer assignment, which
the CPU test backend does not share with the chip, so the residual ledger
is the framework-level contract."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, np, optimizer, remat
from incubator_mxnet_tpu.models.bert import bert_small
from incubator_mxnet_tpu.parallel.sharded import DataParallel


def test_resolve_policy_mapping(monkeypatch):
    import jax

    assert remat.resolve_policy(False) == (False, None)
    assert remat.resolve_policy(None) == (False, None)
    active, pol = remat.resolve_policy(True)
    assert active and pol is jax.checkpoint_policies.nothing_saveable
    active, pol = remat.resolve_policy("dots_saveable")
    assert active and pol is jax.checkpoint_policies.dots_saveable
    with pytest.raises(ValueError):
        remat.resolve_policy("no_such_policy")
    # env parity: DO_MIRROR => nothing_saveable; MEMORY_OPT => dots_saveable
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    active, pol = remat.resolve_policy(None)
    assert active and pol is jax.checkpoint_policies.nothing_saveable
    monkeypatch.delenv("MXNET_BACKWARD_DO_MIRROR")
    monkeypatch.setenv("MXNET_MEMORY_OPT", "1")
    active, pol = remat.resolve_policy(None)
    assert active and pol is jax.checkpoint_policies.dots_saveable


def _bert_loss_fn():
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(out, y):
        scores, _ = out
        return ce(scores.reshape(-1, 1000), y.reshape(-1))

    return mlm_loss


def _step_inputs(batch=2, seq=128, seed=0):
    rng = onp.random.RandomState(seed)
    tokens = np.array(rng.randint(0, 1000, (batch, seq)).astype("int32"))
    labels = np.array(rng.randint(0, 1000, (batch, seq)).astype("int32"))
    return tokens, labels


def test_remat_step_matches_plain_numerically():
    """Same seed, same data: the remat step must produce identical losses
    and parameter updates (recompute changes memory, not math)."""
    def run(remat_spec):
        mx.random.seed(123)
        net = bert_small(max_length=128, dropout=0.1)
        net.initialize()
        dp = DataParallel(net, _bert_loss_fn(),
                          optimizer.Adam(learning_rate=1e-3),
                          remat=remat_spec)
        tokens, labels = _step_inputs()
        losses = [float(dp.step(tokens, labels).asnumpy())
                  for _ in range(2)]
        p0 = next(iter(net.collect_params().values())).data().asnumpy()
        return losses, p0

    l_plain, p_plain = run(False)
    l_remat, p_remat = run(True)
    onp.testing.assert_allclose(l_plain, l_remat, rtol=2e-5)
    onp.testing.assert_allclose(p_plain, p_remat, rtol=2e-4, atol=1e-6)


def test_remat_cuts_saved_residuals_under_cap():
    """The BERT-small train forward at seq 512: full remat must keep its
    forward→backward residual bytes under a cap (2× the step INPUTS)
    that the un-remat forward exceeds by an order of magnitude."""
    import jax

    mx.random.seed(5)
    seq = 512
    net = bert_small(max_length=seq, dropout=0.0)
    net.initialize()
    tokens, labels = _step_inputs(batch=4, seq=seq, seed=1)
    net(tokens)  # deferred init
    loss_fn = _bert_loss_fn()

    def saved_for(spec):
        from incubator_mxnet_tpu import remat as _r
        from incubator_mxnet_tpu.ndarray.ndarray import NDArray
        from incubator_mxnet_tpu.random import trace_key_scope
        from incubator_mxnet_tpu.utils.trace import TraceContext
        from incubator_mxnet_tpu import autograd

        params = [p for p in net.collect_params().values()
                  if p.grad_req != "null"]
        arrays = [p.data() for p in params]

        def fwd(param_vals):
            saved = [(a, a._data) for a in arrays]
            for a, v in zip(arrays, param_vals):
                a._data = v
            try:
                with TraceContext(), trace_key_scope(jax.random.key(0)), \
                        autograd.pause(train_mode=True):
                    out = net.forward(tokens)
                    loss = loss_fn(out, labels)
            finally:
                for a, v in saved:
                    a._data = v
            return loss.mean()._data

        wrapped = _r.wrap(fwd, spec)
        return remat.saved_bytes(wrapped, [a._data for a in arrays])

    plain = saved_for(False)
    full = saved_for(True)
    inputs_bytes = sum(
        int(onp.prod(p.shape)) * 4
        for p in net.collect_params().values()) + tokens.size * 4
    cap = 2 * inputs_bytes
    assert plain > cap, (plain, cap)
    assert full < cap, (full, cap)
    assert full < plain / 10, (full, plain)


def test_hybridize_remat_flag_compiles_and_matches():
    """hybridize(remat='dots_saveable') on a gluon net: same outputs."""
    mx.random.seed(9)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(64, in_units=32, activation="relu"),
            gluon.nn.Dense(32, in_units=64, activation="relu"),
            gluon.nn.Dense(8, in_units=32))
    net.initialize()
    x = np.array(onp.random.RandomState(0)
                 .uniform(-1, 1, (16, 32)).astype("float32"))
    ref = net(x).asnumpy()
    net.hybridize(remat="dots_saveable")
    out1 = net(x).asnumpy()   # eager probe call
    out2 = net(x).asnumpy()   # compiled remat call
    onp.testing.assert_allclose(out1, ref, rtol=1e-6)
    onp.testing.assert_allclose(out2, ref, rtol=1e-5, atol=1e-6)

    # gradient flow through the remat-compiled graph
    from incubator_mxnet_tpu import autograd

    x.attach_grad()
    with autograd.record():
        y = net(x).sum()
    y.backward()
    assert float(onp.abs(x.grad.asnumpy()).sum()) > 0
