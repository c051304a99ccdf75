"""The main path's pallas kernels, compiled for a DESCRIBED TPU v5e.

Interpret mode (what every other kernel test runs under on the CPU) cannot
see what the chip's compiler refuses: a block that overflows the 16 MiB of
scoped VMEM, a slice off the tiling. The TPU compiler is installed here and
compiles for a chip that is described, not attached — so these cases guard
every later PR at no chip time. Nothing runs; a compile that passes is not
a chip run. Skipped where the topology cannot be described.
"""
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from incubator_mxnet_tpu.ops import dropout as dropout_k  # noqa: E402
from incubator_mxnet_tpu.ops import (fused_block, layer_norm,  # noqa: E402
                                     moe, paged_attention, ssm)
from incubator_mxnet_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention)

ROWS = 32 * 512        # BERT-base's batch 32 x seq 512 activation rows


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e chip. The persistent compile cache is
    off in here: an executable compiled for a described chip is written
    but can never be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _ln(x, g, b):
    return layer_norm.layer_norm(x, g, b, interpret=False)


def _rdl(x, h, g, b, s):
    return fused_block.residual_dropout_ln(x, h, g, b, 0.1, s,
                                           interpret=False)


def _gelu_dropout(x, s):
    return fused_block.gelu_dropout(x, 0.1, s, interpret=False)


def _dropout(x, s):
    return dropout_k._dropout_core(x, s, 0.1, False)


def _flash(q, k, v, lens):
    return flash_attention(q, k, v, lengths=lens, impl="pallas",
                           interpret=False)


def _flash_causal(q, k, v):
    return flash_attention(q, k, v, causal=True, impl="pallas",
                           interpret=False)


F32, BF16 = jnp.float32, jnp.bfloat16

# (id, fn, argument kinds, x shape, dtypes, kernels in the grad program)
#   argument kinds: x activation, g (feat,) f32 affine, s (2,) int32 seeds,
#   l (batch,) int32 lengths. Every case compiles jax.grad of the op, which
#   holds the forward AND the backward kernel where backward needs both.
CASES = [
    # layer norm at the widths whose fixed 256-row block overflowed VMEM:
    # f32 backward at 3072, f32 forward at 4096, bf16 backward at 4096,
    # everything at 8192 — forward and backward, f32 and bf16
    ("ln-3072", _ln, "xgg", (ROWS, 3072), (F32, BF16), 2),
    ("ln-4096", _ln, "xgg", (ROWS, 4096), (F32, BF16), 2),
    ("ln-8192", _ln, "xgg", (ROWS, 8192), (F32, BF16), 2),
    # one case per kernel at BERT-base / GPT-2 width
    ("ln-768", _ln, "xgg", (ROWS, 768), (BF16,), 2),
    ("rdl-768", _rdl, "xxggs", (ROWS, 768), (BF16,), 2),
    ("gelu-dropout-3072", _gelu_dropout, "xs", (ROWS, 3072), (BF16,), 1),
    ("dropout-768", _dropout, "xs", (ROWS, 768), (BF16,), 1),
    # flash: BERT's and GPT-2's shapes, and a ragged T with lengths
    ("flash-32x12x512x64-lengths", _flash, "xxxl", (32, 12, 512, 64),
     (BF16,), 3),
    ("flash-8x12x1024x64-causal", _flash_causal, "xxx", (8, 12, 1024, 64),
     (BF16,), 3),
    ("flash-ragged-T100-lengths", _flash, "xxxl", (2, 4, 100, 64),
     (F32,), 3),
]


@pytest.mark.parametrize("fn,kinds,shape,dtypes,n_kernels",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(chip, fn, kinds, shape, dtypes, n_kernels):
    def arg(kind, dtype):
        if kind == "x":
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        if kind == "g":
            return jax.ShapeDtypeStruct(shape[-1:], jnp.float32,
                                        sharding=chip)
        if kind == "s":
            return jax.ShapeDtypeStruct((2,), jnp.int32, sharding=chip)
        return jax.ShapeDtypeStruct(shape[:1], jnp.int32, sharding=chip)

    n_diff = sum(k in "xg" for k in kinds)
    grad = jax.jit(jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                            argnums=tuple(range(n_diff))))
    for dtype in dtypes:
        compiled = grad.lower(*[arg(k, dtype) for k in kinds]).compile()
        assert compiled.as_text().count(
            'custom_call_target="tpu_custom_call"') == n_kernels, dtype


def _pools_are_operands_once(compiled, shape, dtype, at=(1, 2)):
    """One kernel in the program; each pool of `shape` (parameters `at`)
    ONE operand of it, left in HBM for the kernel's own copies; and nothing
    of a pool's size copied or laid out anew (a re-layout of one leaf of a
    cell is 50 MB to 0.5 GB, and would show as temporary bytes)."""
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call, = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    operands = re.findall(
        r"%[\w.]+", call.split("custom-call(", 1)[1].split(")", 1)[0])
    dims = ",".join(str(n) for n in shape)
    for n in at:
        pool, = re.findall(
            rf"(%[\w.]+) = {dtype}\[{dims}\]\S* parameter\({n}\)", text)
        assert operands.count(pool) == 1
    pool_bytes = math.prod(shape) * (2 if dtype == "bf16" else 4)
    assert compiled.memory_analysis().temp_size_in_bytes < min(
        pool_bytes // 2, 32 * 2 ** 20)
    return text


@pytest.mark.parametrize("S,P,d,heads,dtype", [
    (8, 64, 64, 25, F32), (8, 64, 128, 16, F32), (8, 248, 128, 32, BF16)],
    ids=["gpt2xl-25x64", "16x128", "evabyte-32x128-bf16"])
def test_paged_decode_compiles_for_v5e(chip, S, P, d, heads, dtype):
    """`mx_paged_decode` at mx.serve's GPT-2 XL sizes (8 slots x 64 pages
    of 16 tokens, 25 heads of 64, stored two tokens to a row of 128 lanes),
    at a 128-wide float32 head, and at the EvaByte cell's (8 slots x 248
    pages, 32 heads of 128, bfloat16: one query row a head through the MXU).
    The pools as the chip lays them out by itself, each ONE operand of the
    kernel, which fetches a block's pages itself."""
    pt = 16

    def arg(shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pool = arg((S * P + 1, heads) + paged_attention.page_store_shape(pt, d))
    assert paged_attention._on_mxu(arg((S, heads, d)), pool) is (dtype == BF16)
    compiled = jax.jit(
        lambda q, k, v, t, n: paged_attention._pallas_paged_decode(
            q, k, v, t, n, False)).lower(
        arg((S, heads, d)), pool, pool, arg((S, P), jnp.int32),
        arg((S,), jnp.int32)).compile()
    text = _pools_are_operands_once(
        compiled, pool.shape, "bf16" if dtype == BF16 else "f32")
    tiles = "T(8,128)(2,1)" if dtype == BF16 else "T(8,128)"
    assert "{3,2,1,0:%s}" % tiles in text.split("->")[0]     # pages major


def test_mla_decode_compiles_for_v5e(chip):
    """`mx_mla_decode` at the openPangu-Ultra-MoE cell's sizes: 64 slots x
    768 pages of 16 rows, 128 heads against latent rows of 576 values
    stored 640 wide, bfloat16. The pool as the chip lays it out by itself:
    a page one contiguous block, nothing of the pool's size copied; and the
    pool ONE operand of the kernel, which fetches a block's pages itself
    (an operand a page of a block cost more of a grid step than the
    products)."""
    S, H, P, pt, W = 64, 128, 768, 16, 640
    assert paged_attention.latent_store_width(576) == W

    def arg(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    compiled = jax.jit(
        lambda q, pool, t, n: paged_attention._pallas_mla_decode(
            q, pool, t, n, 512, 192 ** -0.5, False)).lower(
        arg((S, H, W)), arg((18240, pt, W)), arg((S, P), jnp.int32),
        arg((S,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "bf16[18240,16,640]{2,1,0:T(8,128)(2,1)}" in text.split("->")[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20
    call, = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    pool, = re.findall(r"(%[\w.]+) = bf16\[18240,16,640\]\S* parameter\(1\)",
                       text)
    operands = call.split("custom-call(", 1)[1].split(")", 1)[0]
    assert re.findall(r"%[\w.]+", operands).count(pool) == 1
    # What the kernel keeps in VMEM, as the compiler placed it: three blocks
    # of 512 x 640 rows, the turned sum (512 x 128 float32), the softmax's
    # two (1, 128) rows, q's and the output's double buffers: 2.87 MB of the
    # 16 MiB a kernel may hold (the weight pushes a step are read from the
    # schedule, `tools/kernel_schedule.py mla_decode`: no HLO shows them)
    used, = re.findall(r'"used_scoped_memory_configs":\[\{"memory_space":'
                       r'"1","offset":"0","size":"(\d+)"\}\]', call)
    blocks = 3 * 512 * W * 2 + 512 * 128 * 4 + 2 * (H * W + H * 512) * 2
    assert blocks <= int(used) < blocks + 2 ** 16 < 16 * 2 ** 20


@pytest.mark.parametrize("tokens,tile,step", [
    (64, 16, "decode"), (128, 128, "decode"), (512, 128, "chunk")],
    ids=["decode-64", "decode-128", "chunk-512"])
def test_moe_experts_compile_for_v5e(chip, monkeypatch, tokens, tile, step):
    """The expert layer at the published widths (7,680 -> 2,048 -> 7,680,
    top-8, 16 of 256 experts held): routing's layout, the two launches of the
    grouped product, the weighted gather back. The kernel's name is the
    step's, whatever tile the step's batch gives it."""
    C, F, E, K = 7680, 2048, 16, 8
    assert moe._tile_rows(tokens * K, 256) == tile
    # `held_experts` asks `_dispatch.interpret_default()`, which sees the CPU
    # here: the kernels are to be compiled for the chip
    monkeypatch.setattr(moe._dispatch, "interpret_default", lambda: False)

    def arg(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    compiled = jax.jit(
        lambda u, ids, w, wg, wu, wd: moe.held_experts(
            u, ids, w, (wg, wu, wd), (0, E), None, step=step,
            impl="pallas", routed=256),
    ).lower(arg((tokens, C)), arg((tokens, K), jnp.int32),
            arg((tokens, K), F32), arg((E, C, F)), arg((E, C, F)),
            arg((E, F, C))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert moe.KERNEL_NAMES[step] in text
    other, = set(moe.KERNEL_NAMES.values()) - {moe.KERNEL_NAMES[step]}
    assert other not in text


def test_ssm_decode_compiles_for_v5e_and_updates_the_state_in_place(chip):
    """`mx_ssm_decode` at the Nemotron-H cell's sizes: 64 slots of 128 heads
    of 64 x 128 float32 state (4 MiB a slot a layer, one block of the
    kernel, stored two heads to a row of 128 lanes), 8 groups. The state leaf, donated, is aliased to the kernel's
    output: nothing of its 256 MiB is copied."""
    S, H, P, N, G = 64, 128, 64, 128, 8

    def arg(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    compiled = jax.jit(
        lambda st, x, b, c, dt, a, d, act: ssm._pallas_decode(
            st, x, b, c, dt, a, d, act, False), donate_argnums=(0,)).lower(
        arg((S,) + ssm.state_store_shape(H, P, N, G)), arg((S, H, P)),
        arg((S, G, N)), arg((S, G, N)),
        arg((S, H)), arg((H,)), arg((H,)), arg((S,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert ssm.KERNEL_NAME in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == S * H * P * N * 4
    assert mem.temp_size_in_bytes < 32 * 2 ** 20


def test_paged_decode_grouped_heads_compile_for_v5e(chip):
    """`mx_paged_decode` with 32 query heads over 2 stored heads of 128
    (the Nemotron-H cell's one attention block: 64 slots x 192 pages of 16
    tokens, bfloat16): one kernel, a block of 32 pages fetched once for its
    16 query heads a stored head and multiplied on the MXU, each pool one
    operand."""
    S, P, pt, hq, hk, d = 64, 192, 16, 32, 2, 128

    def arg(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pool = arg((S * P + 1, hk) + paged_attention.page_store_shape(pt, d))
    assert paged_attention._on_mxu(arg((S, hq, d)), pool)
    compiled = jax.jit(
        lambda q, k, v, t, n: paged_attention._pallas_paged_decode(
            q, k, v, t, n, False)).lower(
        arg((S, hq, d)), pool, pool, arg((S, P), jnp.int32),
        arg((S,), jnp.int32)).compile()
    assert "mx_paged_decode" in _pools_are_operands_once(
        compiled, pool.shape, "bf16")


@pytest.mark.parametrize("tokens,tile,step", [
    (64, 16, "decode"), (128, 128, "chunk"), (512, 128, "chunk")],
    ids=["decode-64", "chunk-128", "chunk-512"])
def test_ungated_experts_compile_for_v5e(chip, monkeypatch, tokens, tile,
                                         step):
    """The latent expert layer at the published widths (1,024 -> 2,688 ->
    1,024, top-22, 128 of 512 experts held, ungated relu^2): two launches
    under the step's name, at the tile the rows an expert can expect give."""
    C, F, E, K = 1024, 2688, 128, 22
    assert moe._tile_rows(tokens * K, 512) == tile
    monkeypatch.setattr(moe._dispatch, "interpret_default", lambda: False)

    def arg(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    compiled = jax.jit(
        lambda u, ids, w, w1, w2: moe.held_experts(
            u, ids, w, (w1, w2), (0, E), None, step=step, impl="pallas",
            routed=512),
    ).lower(arg((tokens, C)), arg((tokens, K), jnp.int32),
            arg((tokens, K), F32), arg((E, C, F)), arg((E, F, C))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert moe.KERNEL_NAMES[step] in text
