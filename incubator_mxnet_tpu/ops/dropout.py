"""Dropout as a pallas TPU kernel over the on-chip hardware RNG.

Why a kernel at all: dropout is the classic "free-looking op that isn't" —
measured on one v5e chip, BERT-base training spends ~40% of its step time
generating threefry random bits on the VPU (88k tok/s with jax.random
bernoulli dropout vs 144k with dropout off). The reference hits the same
wall differently: its GPU dropout uses cuDNN's stateful generator
(`src/operator/nn/dropout-inl.h`), not a counter-based PRNG recomputed per
element. The TPU-native answer is the per-core hardware PRNG
(`pltpu.prng_seed` / `prng_random_bits`): seed once per (call, block),
draw 32 raw bits per element, compare against a uint32 threshold.

Backward recomputes the mask from the same seed instead of saving it —
zero residual memory traffic for the mask (the same trick flash attention
uses for probabilities).

Numerics: keep-probability is exact to 2^-32; the drawn bits are
independent of the jax.random stream but deterministic given the folded-in
framework key, so `mx.random.seed` reproducibility holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch


def _mask_kernel_body(seed_ref, x_ref, o_ref, *, threshold, scale, grad):
    # distinct stream per block: fold the block index into the seed pair
    # (the TPU seed primitive takes at most two words)
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0), seed_ref[1])
    bits = pltpu.prng_random_bits(x_ref.shape)
    keep = bits.astype(jnp.uint32) >= jnp.uint32(threshold)
    src = x_ref[...]
    o_ref[...] = jnp.where(keep, src * scale, 0.0).astype(o_ref.dtype)
    del grad  # fwd and bwd bodies are identical: y = mask(x), dx = mask(dy)


def _emulate(x2d, seeds, threshold, scale):
    """Off-TPU stand-in: `pltpu.prng_seed` has no CPU lowering (not even in
    interpret mode), so non-TPU backends draw deterministically from the
    same seed pair via jax.random. Bit-exact parity with the hardware
    generator is impossible; the CONTRACT (mask/scale semantics, fwd/bwd
    mask identity, per-seed determinism) is identical and pinned by
    tests/test_dropout_kernel.py."""
    import jax.random as jr

    key = jr.fold_in(jr.PRNGKey(seeds[0]), seeds[1])
    bits = jr.bits(key, x2d.shape, jnp.uint32)
    keep = bits >= jnp.uint32(threshold)
    return jnp.where(keep, x2d * scale, 0).astype(x2d.dtype)


def _run_kernel(x2d, seeds, threshold, scale, interpret, grad):
    if interpret:
        del grad
        return _emulate(x2d, seeds, threshold, scale)
    rows, cols = x2d.shape
    # block rows sized to keep the (block, cols) tile within ~2 MB VMEM
    target = max(1, (2 << 20) // max(1, cols * x2d.dtype.itemsize))
    block = max(8, min(1024, target) // 8 * 8)  # sublane-tiled: multiple of 8
    if rows < block:
        block = rows
    grid = (rows + block - 1) // block
    return pl.pallas_call(
        functools.partial(_mask_kernel_body, threshold=threshold,
                          scale=scale, grad=grad),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((block, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
        name="mx_dropout",
    )(seeds, x2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dropout_core(x2d, seeds, p, interpret):
    threshold = min(int(p * 4294967296.0), 4294967295)
    return _run_kernel(x2d, seeds, threshold, 1.0 / (1.0 - p), interpret,
                       grad=False)


def _dropout_core_fwd(x2d, seeds, p, interpret):
    return _dropout_core(x2d, seeds, p, interpret), seeds


def _dropout_core_bwd(p, interpret, seeds, dy):
    import numpy as onp

    threshold = min(int(p * 4294967296.0), 4294967295)
    dx = _run_kernel(dy, seeds, threshold, 1.0 / (1.0 - p), interpret,
                     grad=True)
    return dx, onp.zeros(seeds.shape, jax.dtypes.float0)


_dropout_core.defvjp(_dropout_core_fwd, _dropout_core_bwd)


def supports(shape, axes, dtype, p=0.5):
    """Kernel eligibility: plain (non-broadcast) dropout with 0<p<1 on
    shapes whose trailing dim tiles the 128-lane VPU; anything else falls
    back to the jax.random path."""
    if axes:
        return False
    if not jnp.issubdtype(dtype, jnp.floating):  # covers bf16 (kind 'V')
        return False
    if not 0.0 < p < 1.0:   # p=1 would divide by zero in the kernel scale;
        return False        # the jax.random fallback handles it (all-zero)
    if len(shape) == 0:
        return False
    size = 1
    for s in shape:
        size *= s
    return size >= 1024 and (shape[-1] % 128 == 0 or size % 1024 == 0)


def use_kernel(key):
    """The pallas kernel beats threefry dropout (113k vs 88k BERT tok/s on
    v5e) but loses to the fully-fused XLA path when keys are rbg-class
    (124k) — a kernel boundary costs more than hardware bit-gen saves. So:
    kernel only for threefry keys on a real TPU."""
    if not _dispatch.use_pallas():
        return False
    if jnp.issubdtype(getattr(key, "dtype", None), jax.dtypes.prng_key):
        return "fry" in str(jax.random.key_impl(key))
    return True  # legacy uint32 key arrays are threefry


def dropout(x, key, p):
    """Hardware-RNG dropout: y = x/(1-p) where kept, 0 where dropped.

    `key` is a jax PRNG key (any impl); its raw words seed the on-chip
    generator so each framework-level draw gets an independent stream.
    """
    if jnp.issubdtype(getattr(key, "dtype", None), jax.dtypes.prng_key):
        raw = jax.random.key_data(key)
    else:
        raw = key  # legacy uint32 key array
    seeds = raw.reshape(-1)[:2].astype(jnp.int32)
    if seeds.shape[0] < 2:
        seeds = jnp.concatenate([seeds, jnp.zeros((1,), jnp.int32)])
    shape = x.shape
    if shape[-1] % 128 == 0:
        x2d = x.reshape(-1, shape[-1])
    else:
        x2d = x.reshape(-1, 1024)
    out = _dropout_core(x2d, seeds, float(p), _dispatch.interpret_default())
    return out.reshape(shape)
