"""Every pallas kernel of the main path carries a stable name, so that a
profiler trace names it after a refactor (`mx_*` in the ``XLA Ops`` lane)
and a per-kernel metric can find it. Lowered for the TPU from shapes, on
the CPU: nothing compiles for a chip and nothing runs."""
import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.ops import dropout as dropout_k
from incubator_mxnet_tpu.ops import (fused_block, layer_norm, moe,
                                     paged_attention, ssm)
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
# the MLA family's decode step: 4 heads against latent rows of 32 + 8 values
# stored 128 wide, and a grouped expert product over 6 held experts
MLA_Q = jax.ShapeDtypeStruct((4, 4, 128), jnp.float32)
MLA_POOL = jax.ShapeDtypeStruct((33, 16, 128), jnp.float32)
MOE_X = {t: jax.ShapeDtypeStruct((3 * t, 128), jnp.float32) for t in (16, 128)}
MOE_TILES = jax.ShapeDtypeStruct((3,), jnp.int32)
MOE_N = jax.ShapeDtypeStruct((), jnp.int32)
MOE_IN = jax.ShapeDtypeStruct((6, 128, 256), jnp.float32)
MOE_OUT = jax.ShapeDtypeStruct((6, 256, 128), jnp.float32)


# a state-space decode step: 4 slots of 8 heads of 16 x 128 state, 2 groups
SSM = tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in (
    (4,) + ssm.state_store_shape(8, 16, 128, 2), (4, 8, 16), (4, 2, 128), (4, 2, 128), (4, 8), (8,),
    (8,))) + (jax.ShapeDtypeStruct((4,), jnp.bool_),)


def _moe(tile, step):
    return (lambda x, te, n, wg, wu, wd: moe._pallas_grouped_ffn(
        x, te, n, wg, wu, wd, tile, moe.KERNEL_NAMES[step], False),
        (), (MOE_X[tile], MOE_TILES, MOE_N, MOE_IN, MOE_IN, MOE_OUT))


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
    "mla_decode": (
        lambda q, pool, t, n: paged_attention._pallas_mla_decode(
            q, pool, t, n, 32, 0.2, False),
        (), (MLA_Q, MLA_POOL, TABLE, LENGTHS)),
    "ssm_decode": (lambda *a: ssm._pallas_decode(*a, False), (), SSM),
    "moe_experts": _moe(16, "decode"),
    "moe_chunk_experts": _moe(128, "chunk"),
}
# kernel name -> the op whose forward-and-backward program holds it
KERNELS = {
    "mx_ln_fwd": "ln", "mx_ln_bwd": "ln",
    "mx_rdln_fwd": "rdln", "mx_rdln_bwd": "rdln",
    "mx_gelu_dropout": "gelu_dropout", "mx_dropout": "dropout",
    "mx_flash_fwd": "flash", "mx_flash_dq": "flash", "mx_flash_dkv": "flash",
    "mx_paged_decode": "paged_decode", "mx_mla_decode": "mla_decode",
    "mx_ssm_decode": "ssm_decode", "mx_moe_experts": "moe_experts",
    "mx_moe_chunk_experts": "moe_chunk_experts",
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
    """Every call a name: a call added without one shows here. (`ops/moe.py`
    has one call, named by its tile: a decode step's and a chunk's.)"""
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
    src = inspect.getsource(moe)
    assert len(re.findall(r"pl\.pallas_call\(", src)) == 1
    assert "name=name," in src and "KERNEL_NAMES[step]" in src
    names += moe.KERNEL_NAMES.values()
    src = inspect.getsource(ssm)
    assert len(re.findall(r"pl\.pallas_call\(", src)) == 1
    assert "name=KERNEL_NAME," in src
    names.append(ssm.KERNEL_NAME)
    assert sorted(names) == sorted(KERNELS)
