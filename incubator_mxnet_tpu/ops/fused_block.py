"""Fused residual + dropout + LayerNorm as one pallas TPU kernel.

The post-LN transformer cell computes ``ln(x + dropout(h))`` twice per
layer — in BERT-base that is 24 sites, each touching a (B·T, C)
activation. Left to XLA this is 4-5 HBM passes per site forward (mask
bits, masked h, the sum, the stats, the normalize) and more backward
(saved mask read, softmax-style LN backward); profiling the seq-512
train step shows the dropout/add/LN chain costing ~45 ms of a 143 ms
step (`divide_subtract_fusion` + `convert_add_fusion` +
`multiply_reduce_fusion` lanes).

Fused: forward reads x and h ONCE, draws the dropout mask from the
on-chip hardware PRNG (`pltpu.prng_seed` / `prng_random_bits`), and
writes the normalized output plus tiny (rows,) f32 stats — 2 reads,
1 write. Backward re-seeds the same stream to recompute the mask and
the pre-norm sum (zero mask/activation residuals — the trick
`ops/dropout.py` and flash attention already use), emitting dx, dh and
the per-block dgamma/dbeta partials in one pass.

Reference role: the fused dropout-add-LN the reference gets from oneDNN
subgraph rewrites on CPU (`src/operator/subgraph/dnnl/`), built
TPU-native instead.

Off-TPU the same semantics run as plain jnp ops (jax.random mask) so
the contract is testable on the CPU mesh; bit-exact parity with the
hardware generator is impossible, matching the `ops/dropout.py`
emulation discipline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch


def supports(shape, feat):
    """Last-axis LN over a lane-aligned feature dim, like ops/layer_norm."""
    return feat % 128 == 0 and feat <= 8192 and len(shape) >= 2


def _threshold(p):
    return min(int(p * 4294967296.0), 4294967295)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _mask(seed_ref, shape, threshold):
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0), seed_ref[1])
    bits = pltpu.prng_random_bits(shape)
    return bits.astype(jnp.uint32) >= jnp.uint32(threshold)


def _fwd_kernel(seed_ref, x_ref, h_ref, g_ref, b_ref,
                y_ref, m_ref, r_ref, *, threshold, scale, eps, use_rng):
    x = x_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    if use_rng:
        keep = _mask(seed_ref, x_ref.shape, threshold)
        s = x + jnp.where(keep, h * scale, 0.0)
    else:
        s = x + h
    c = s.shape[1]
    mean = jnp.sum(s, axis=1, keepdims=True) / c
    sc = s - mean
    var = jnp.sum(sc * sc, axis=1, keepdims=True) / c
    rstd = jax.lax.rsqrt(var + eps)
    y = sc * rstd * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    m_ref[...] = mean
    r_ref[...] = rstd


def _bwd_kernel(seed_ref, x_ref, h_ref, dy_ref, m_ref, r_ref, g_ref,
                dx_ref, dh_ref, dgb_ref, acc_scr, *,
                threshold, scale, eps, use_rng, n_blocks):
    del eps
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    if use_rng:
        keep = _mask(seed_ref, x_ref.shape, threshold)
        s = x + jnp.where(keep, h * scale, 0.0)
    else:
        s = x + h
    mean, rstd = m_ref[...], r_ref[...]
    g = g_ref[...].astype(jnp.float32)
    c = s.shape[1]
    xhat = (s - mean) * rstd
    wdy = dy * g
    c1 = jnp.sum(wdy, axis=1, keepdims=True) / c
    c2 = jnp.sum(wdy * xhat, axis=1, keepdims=True) / c
    ds = (wdy - c1 - xhat * c2) * rstd
    dx_ref[...] = ds.astype(dx_ref.dtype)
    if use_rng:
        dh = jnp.where(keep, ds * scale, 0.0)
    else:
        dh = ds
    dh_ref[...] = dh.astype(dh_ref.dtype)
    acc_scr[0:1, :] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    acc_scr[1:2, :] += jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == n_blocks - 1)
    def _fini():
        dgb_ref[...] = acc_scr[...]


def _block_rows(rows, cols, itemsize):
    # sized for the BACKWARD kernel's VMEM footprint (x, h, dy upcast to
    # f32 + dx, dh + scratch, double-buffered): ~6 live f32 tiles must fit
    # the 16 MB scoped window. fwd and bwd MUST share the block size — the
    # dropout mask stream is seeded per (seed, program_id) block.
    target = max(8, (1 << 20) // max(1, cols * itemsize))
    block = max(8, min(256, target) // 8 * 8)
    return block if rows >= block else rows


def _fwd(x2d, h2d, gamma, beta, seeds, p, eps, interpret):
    rows, feat = x2d.shape
    block = _block_rows(rows, feat, x2d.dtype.itemsize)
    n_blocks = rows // block
    kernel = functools.partial(
        _fwd_kernel, threshold=_threshold(p), scale=1.0 / (1.0 - p) if p else 1.0,
        eps=eps, use_rng=p > 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block, feat), lambda i, s: (i, 0)),
            pl.BlockSpec((block, feat), lambda i, s: (i, 0)),
            pl.BlockSpec((1, feat), lambda i, s: (0, 0)),
            pl.BlockSpec((1, feat), lambda i, s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, feat), lambda i, s: (i, 0)),
            pl.BlockSpec((block, 1), lambda i, s: (i, 0)),
            pl.BlockSpec((block, 1), lambda i, s: (i, 0)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, feat), x2d.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mx_rdln_fwd",
    )(seeds, x2d, h2d, gamma.reshape(1, feat), beta.reshape(1, feat))


def _bwd(x2d, h2d, dy2d, mean, rstd, gamma, seeds, p, eps, interpret):
    rows, feat = x2d.shape
    block = _block_rows(rows, feat, x2d.dtype.itemsize)
    n_blocks = rows // block
    kernel = functools.partial(
        _bwd_kernel, threshold=_threshold(p),
        scale=1.0 / (1.0 - p) if p else 1.0, eps=eps, use_rng=p > 0,
        n_blocks=n_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block, feat), lambda i, s: (i, 0)),
            pl.BlockSpec((block, feat), lambda i, s: (i, 0)),
            pl.BlockSpec((block, feat), lambda i, s: (i, 0)),
            pl.BlockSpec((block, 1), lambda i, s: (i, 0)),
            pl.BlockSpec((block, 1), lambda i, s: (i, 0)),
            pl.BlockSpec((1, feat), lambda i, s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, feat), lambda i, s: (i, 0)),
            pl.BlockSpec((block, feat), lambda i, s: (i, 0)),
            pl.BlockSpec((8, feat), lambda i, s: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((8, feat), jnp.float32)],
    )
    dx, dh, dgb = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, feat), x2d.dtype),
            jax.ShapeDtypeStruct((rows, feat), h2d.dtype),
            jax.ShapeDtypeStruct((8, feat), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mx_rdln_bwd",
    )(seeds, x2d, h2d, dy2d, mean, rstd, gamma.reshape(1, feat))
    return dx, dh, dgb[0], dgb[1]


# ---------------------------------------------------------------------------
# differentiable core + public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _core(x2d, h2d, gamma, beta, seeds, p, eps, interpret):
    y, _, _ = _fwd(x2d, h2d, gamma, beta, seeds, p, eps, interpret)
    return y


def _core_fwd(x2d, h2d, gamma, beta, seeds, p, eps, interpret):
    y, mean, rstd = _fwd(x2d, h2d, gamma, beta, seeds, p, eps, interpret)
    return y, (x2d, h2d, gamma, seeds, mean, rstd)


def _core_bwd(p, eps, interpret, res, dy):
    import numpy as onp

    x2d, h2d, gamma, seeds, mean, rstd = res
    dx, dh, dg, db = _bwd(x2d, h2d, dy, mean, rstd, gamma, seeds, p, eps,
                          interpret)
    return (dx, dh, dg.astype(gamma.dtype), db.astype(gamma.dtype),
            onp.zeros(seeds.shape, jax.dtypes.float0))


_core.defvjp(_core_fwd, _core_bwd)


def _emulate(x, h, gamma, beta, seeds, p, eps):
    """Off-TPU path: identical contract via jnp + jax.random (plain
    autodiff — no custom vjp needed off-chip)."""
    import jax.random as jr

    if p > 0:
        key = jr.fold_in(jr.PRNGKey(seeds[0]), seeds[1])
        keep = jr.bits(key, x.shape, jnp.uint32) >= jnp.uint32(_threshold(p))
        s = x.astype(jnp.float32) \
            + jnp.where(keep, h.astype(jnp.float32) / (1.0 - p), 0.0)
    else:
        s = x.astype(jnp.float32) + h.astype(jnp.float32)
    mean = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.var(s, axis=-1, keepdims=True)
    y = (s - mean) * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32) \
        + beta.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# fused gelu + dropout (the FFN hidden-activation site)
# ---------------------------------------------------------------------------
#
# dropout(gelu(u)) on the (B·T, 4C) FFN hidden is the largest dropout in a
# transformer (402 MB bf16 at BERT-base seq-512); XLA's path writes and
# re-reads the RNG bit tensor through HBM (~200 MB per site) and saves the
# keep mask for backward. The kernel draws bits in VMEM and backward
# re-seeds the same stream — the bit/mask tensors never touch HBM.
# erf has no pallas TPU lowering, so Φ uses the Abramowitz–Stegun 7.1.26
# rational approximation (|err| < 1.5e-7 — below bf16 resolution).

_AS_P = 0.3275911
_AS_A = (0.254829592, -0.284496736, 1.421413741, -1.453152027, 1.061405429)


def _erf_approx(z):
    s = jnp.sign(z)
    za = jnp.abs(z)
    t = 1.0 / (1.0 + _AS_P * za)
    poly = t * (_AS_A[0] + t * (_AS_A[1] + t * (
        _AS_A[2] + t * (_AS_A[3] + t * _AS_A[4]))))
    return s * (1.0 - poly * jnp.exp(-za * za))


def _gelu_parts(u):
    """(gelu(u), gelu'(u)) in f32: Φ(u) via erf approx; φ(u) closed-form."""
    phi_cdf = 0.5 * (1.0 + _erf_approx(u * 0.7071067811865476))
    pdf = jnp.exp(-0.5 * u * u) * 0.3989422804014327
    return u * phi_cdf, phi_cdf + u * pdf


def _gd_fwd_kernel(seed_ref, u_ref, h_ref, *, threshold, scale, use_rng):
    u = u_ref[...].astype(jnp.float32)
    g, _ = _gelu_parts(u)
    if use_rng:
        keep = _mask(seed_ref, u_ref.shape, threshold)
        g = jnp.where(keep, g * scale, 0.0)
    h_ref[...] = g.astype(h_ref.dtype)


def _gd_bwd_kernel(seed_ref, u_ref, dy_ref, du_ref, *,
                   threshold, scale, use_rng):
    u = u_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    _, dg = _gelu_parts(u)
    if use_rng:
        keep = _mask(seed_ref, u_ref.shape, threshold)
        du = jnp.where(keep, dy * dg * scale, 0.0)
    else:
        du = dy * dg
    du_ref[...] = du.astype(du_ref.dtype)


def _gd_call(kernel, out_dtype, x2d, seeds, extra, p, interpret):
    rows, feat = x2d.shape
    block = _block_rows(rows, feat, x2d.dtype.itemsize)
    n_blocks = rows // block
    k = functools.partial(kernel, threshold=_threshold(p),
                          scale=1.0 / (1.0 - p) if p else 1.0,
                          use_rng=p > 0)
    in_specs = [pl.BlockSpec((block, feat), lambda i, s: (i, 0))
                for _ in range(1 + len(extra))]
    return pl.pallas_call(
        k,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block, feat), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, feat), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mx_gelu_dropout",
    )(seeds, x2d, *extra)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gd_core(u2d, seeds, p, interpret):
    return _gd_call(_gd_fwd_kernel, u2d.dtype, u2d, seeds, (), p, interpret)


def _gd_core_fwd(u2d, seeds, p, interpret):
    return _gd_core(u2d, seeds, p, interpret), (u2d, seeds)


def _gd_core_bwd(p, interpret, res, dy):
    import numpy as onp

    u2d, seeds = res
    du = _gd_call(_gd_bwd_kernel, u2d.dtype, u2d, seeds, (dy,), p, interpret)
    return du, onp.zeros(seeds.shape, jax.dtypes.float0)


_gd_core.defvjp(_gd_core_fwd, _gd_core_bwd)


def _gd_emulate(u, seeds, p):
    import jax.random as jr

    g = jax.nn.gelu(u.astype(jnp.float32), approximate=False)
    if p > 0:
        key = jr.fold_in(jr.PRNGKey(seeds[0]), seeds[1])
        keep = jr.bits(key, u.shape, jnp.uint32) >= jnp.uint32(_threshold(p))
        g = jnp.where(keep, g / (1.0 - p), 0.0)
    return g.astype(u.dtype)


def gelu_dropout(u, p, seeds, interpret=None):
    """``dropout_p(gelu(u))`` over the last axis, one fused pass with
    in-VMEM RNG (backward re-seeds the stream; no mask/bit residuals)."""
    if interpret is None:
        interpret = _dispatch.interpret_default()
    if interpret:
        return _gd_emulate(u, seeds, float(p))
    shape = u.shape
    feat = shape[-1]
    rows = 1
    for s_ in shape[:-1]:
        rows *= s_
    if rows == 0:
        # empty batch: no grid to launch (block would be 0 → pad divides
        # by zero); the contract output is just the empty input shape
        return u
    u2d = u.reshape(rows, feat)
    block = _block_rows(rows, feat, u2d.dtype.itemsize)
    pad = (-rows) % block if block else 0
    if pad:
        u2d = jnp.pad(u2d, ((0, pad), (0, 0)))
    h = _gd_core(u2d, jnp.asarray(seeds, jnp.int32), float(p),
                 bool(interpret))
    if pad:
        h = h[:rows]
    return h.reshape(shape)


def residual_dropout_ln(x, h, gamma, beta, p, seeds, eps=1e-5,
                        interpret=None):
    """``layer_norm(x + dropout_p(h))`` over the last axis, one fused pass.

    x, h: same-shape activations (leading axes collapse to rows);
    gamma/beta: (C,) affine params; seeds: (2,) int32 PRNG words (a fresh
    framework key per call — reproducible under `mx.random.seed`).
    """
    if interpret is None:
        interpret = _dispatch.interpret_default()
    shape = x.shape
    feat = shape[-1]
    if interpret:
        return _emulate(x, h, gamma, beta, seeds, float(p), float(eps))
    rows = 1
    for s_ in shape[:-1]:
        rows *= s_
    if rows == 0:
        # empty batch: no grid to launch (block would be 0 → pad divides
        # by zero); ln of nothing is nothing
        return x
    x2d = x.reshape(rows, feat)
    h2d = h.reshape(rows, feat)
    block = _block_rows(rows, feat, x2d.dtype.itemsize)
    pad = (-rows) % block if block else 0
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
        h2d = jnp.pad(h2d, ((0, pad), (0, 0)))
    y = _core(x2d, h2d, gamma, beta, jnp.asarray(seeds, jnp.int32),
              float(p), float(eps), bool(interpret))
    if pad:
        y = y[:rows]
    return y.reshape(shape)
