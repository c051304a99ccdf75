"""Every pallas kernel of the main path carries a stable name, so that a
profiler trace names it after a refactor (`mx_*` in the ``XLA Ops`` lane)
and a per-kernel metric can find it. Lowered for the TPU from shapes, on
the CPU: nothing compiles for a chip and nothing runs."""
import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.ops import dropout as dropout_k
from incubator_mxnet_tpu.ops import fused_block, layer_norm, paged_attention
from incubator_mxnet_tpu.ops.flash_attention import flash_attention

ROWS, FEAT = 256, 256
X = jax.ShapeDtypeStruct((ROWS, FEAT), jnp.float32)
G = jax.ShapeDtypeStruct((FEAT,), jnp.float32)
S = jax.ShapeDtypeStruct((2,), jnp.int32)
QKV = jax.ShapeDtypeStruct((2, 2, 256, 64), jnp.float32)
# serve's decode step: 4 slots of 8 pages, a pool of 33 pages of 16 tokens
# (two heads of 64: stored two tokens to a row of 128 lanes)
PAGED_Q = jax.ShapeDtypeStruct((4, 2, 64), jnp.float32)
POOL = jax.ShapeDtypeStruct((33, 2, 8, 128), jnp.float32)
TABLE = jax.ShapeDtypeStruct((4, 8), jnp.int32)
LENGTHS = jax.ShapeDtypeStruct((4,), jnp.int32)

# op, its differentiable arguments (none: forward only), the rest
OPS = {
    "ln": (lambda x, g, b: layer_norm.layer_norm(x, g, b, interpret=False),
           (X, G, G), ()),
    "rdln": (lambda x, h, g, b, s: fused_block.residual_dropout_ln(
        x, h, g, b, 0.1, s, interpret=False), (X, X, G, G), (S,)),
    "gelu_dropout": (lambda x, s: fused_block.gelu_dropout(
        x, 0.1, s, interpret=False), (X,), (S,)),
    "dropout": (lambda x, s: dropout_k._dropout_core(x, s, 0.1, False),
                (X,), (S,)),
    "flash": (lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl="pallas", interpret=False),
        (QKV, QKV, QKV), ()),
    "paged_decode": (
        lambda q, k, v, t, n: paged_attention._pallas_paged_decode(
            q, k, v, t, n, False),
        (), (PAGED_Q, POOL, POOL, TABLE, LENGTHS)),
}
# kernel name -> the op whose forward-and-backward program holds it
KERNELS = {
    "mx_ln_fwd": "ln", "mx_ln_bwd": "ln",
    "mx_rdln_fwd": "rdln", "mx_rdln_bwd": "rdln",
    "mx_gelu_dropout": "gelu_dropout", "mx_dropout": "dropout",
    "mx_flash_fwd": "flash", "mx_flash_dq": "flash", "mx_flash_dkv": "flash",
    "mx_paged_decode": "paged_decode",
}


@pytest.fixture(scope="module")
def lowered():
    """The TPU lowering of each op's gradient program (of the op itself
    where nothing is differentiated), as text."""
    out = {}
    for op, (fn, diff, rest) in OPS.items():
        if diff:
            fn = jax.grad(
                lambda *a, _fn=fn: _fn(*a).astype(jnp.float32).sum(),
                argnums=tuple(range(len(diff))))
        out[op] = jax.jit(fn).trace(*diff, *rest).lower(
            lowering_platforms=("tpu",)).as_text()
    return out


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_pallas_kernel_is_named_in_its_lowering(lowered, kernel):
    text = lowered[KERNELS[kernel]]
    assert "tpu_custom_call" in text
    assert kernel in text


def test_the_roll_is_a_named_program_and_scope():
    """`mx_eva_roll` (serve/eva.py) is XLA, not pallas: the name is the
    jitted program's (the ``XLA Modules`` lane reads ``jit_mx_eva_roll``) and
    a named scope on every op in it."""
    from incubator_mxnet_tpu.models.evabyte import (EvaByteConfig,
                                                    EvaByteDecoder)
    from incubator_mxnet_tpu.serve.eva import EvaSlotDecoder

    cfg = EvaByteConfig(num_hidden_layers=1, hidden_size=256,
                        num_attention_heads=2, intermediate_size=64,
                        vocab_size=8, num_pred_heads=1, window_size=64,
                        chunk_size=4, max_position_embeddings=256)
    top, layer = cfg.leaf_shapes()
    params = {n: jnp.zeros(s) for n, s in top.items()}
    params["layers"] = [{n: jnp.zeros(s) for n, s in layer.items()}]
    slots = EvaSlotDecoder(EvaByteDecoder(cfg, params), max_slots=2,
                           page_tokens=4, prefill_chunk=16)
    leaf = (jax.ShapeDtypeStruct((9, 2, 4, 128), jnp.bfloat16),)
    feats = ((jax.ShapeDtypeStruct((2, 128), jnp.float32),) * 2,)
    pages = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)  # noqa: E731
    text = slots._build_roll()._fn.trace(
        feats, {"k": leaf, "v": leaf}, pages(16), pages(4)).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "jit_mx_eva_roll" in text
    assert text.count("mx_eva_roll/") > 10


def test_every_pallas_call_of_the_main_path_is_named():
    """Ten calls, ten names: a call added without one shows here."""
    import inspect
    import re
    import sys

    flash_mod = sys.modules[flash_attention.__module__]
    names = []
    for mod in (dropout_k, flash_mod, fused_block, layer_norm,
                paged_attention):
        src = inspect.getsource(mod)
        calls = len(re.findall(r"pl\.pallas_call\(", src))
        found = re.findall(r'name="(mx_[a-z_]+)"', src)
        assert calls == len(found), mod.__name__
        names += found
    assert sorted(names) == sorted(KERNELS)
