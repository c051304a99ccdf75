"""Flash attention (forward + backward) as pallas TPU kernels.

Memory-linear attention: O(T) live memory instead of the O(T^2) score
matrix, with the online-softmax recurrence. Forward saves only the
per-row logsumexp; backward recomputes probabilities blockwise.

Reference role: the fused self-attention the reference only has as a CPU
oneDNN subgraph (`src/operator/subgraph/dnnl/dnnl_transformer_qk_property.h`,
`dnnl_transformer_valid_mask.cc`); here it is a first-class TPU kernel
feeding the MXU with (block_q × block_k) bf16 tiles and f32 accumulators.

Structure: 3D grid (batch·heads, q-blocks, kv-blocks). The kv axis is the
innermost ("arbitrary") dimension; running max / sum / output accumulate in
VMEM scratch across kv steps and spill to HBM once per q-block, so VMEM
usage is independent of sequence length. Pallas double-buffers the K/V
block DMAs against compute. Causal masking skips fully-masked kv blocks.

Layout: q/k/v are (batch, heads, seq, head_dim). Padding/causal masking is
expressed with a per-sequence `lengths` vector, not a dense (T, T) mask —
a dense mask would defeat the memory linearity.

On CPU backends (the virtual 8-device test mesh) the kernels run in
pallas interpret mode, so numerics are testable without a TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch

NEG_INF = -1.0e30  # finite stand-in for -inf: keeps exp()/max() NaN-free



def _round_up(x, m):
    return (x + m - 1) // m * m


def _dot(a, b, ta=False, tb=False):
    """Tile matmul on the MXU in the operands' dtype, f32 accumulation."""
    dims = (((0 if ta else 1,), (1 if tb else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                sm_scale, causal, block_q, block_k, n_kv, need_mask,
                have_lengths):
    qi, kj = pl.program_id(1), pl.program_id(2)
    kv_len = len_ref[pl.program_id(0)]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip kv blocks strictly above the diagonal band
    run = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0]                                   # (bq, D) input dtype
        k = k_ref[0]
        v = v_ref[0]
        s = _dot(q, k, tb=True) * sm_scale             # (bq, bk) f32
        if need_mask:
            rows = (qi * block_q
                    + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
            cols = (kj * block_k
                    + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
            mask = cols < kv_len
            if causal:
                mask = jnp.logical_and(mask, cols <= rows)
            s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if need_mask:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _dot(p.astype(v.dtype), v)

    @pl.when(kj == n_kv - 1)
    def _fini():
        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        l_safe = jnp.where(l > 0, l, 1.0)
        o = acc / l_safe
        if have_lengths:
            # self-attention row-validity: zero rows past the sequence
            # length; +inf lse makes backward's exp(s - lse) vanish there
            rows = (qi * block_q
                    + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
            valid = rows < kv_len
            o = jnp.where(valid, o, 0.0)
            lse = jnp.where(jnp.logical_and(l > 0, valid),
                            m + jnp.log(l_safe), jnp.inf)
        elif need_mask:
            # kv padding / causal only: rows stay live; guard empty rows
            lse = jnp.where(l > 0, m + jnp.log(l_safe), jnp.inf)
        else:
            lse = m + jnp.log(l_safe)
        o_ref[0] = o.astype(o_ref.dtype)
        lse_ref[0] = lse


def _fwd(q, k, v, lens, sm_scale, causal, block_q, block_k, interpret,
         need_mask, have_lengths):
    bh, tq, d = q.shape
    tk = k.shape[1]
    n_q, n_kv = tq // block_q, tk // block_k
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kv=n_kv, need_mask=need_mask,
        have_lengths=have_lengths)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, lens: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, lens: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, lens: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, lens: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j, lens: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mx_flash_fwd",
    )(lens, q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *,
               sm_scale, causal, block_q, block_k, n_kv, need_mask):
    qi, kj = pl.program_id(1), pl.program_id(2)
    kv_len = len_ref[pl.program_id(0)]

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]
        lse, delta = lse_ref[0], delta_ref[0]          # (bq, 1) f32
        s = _dot(q, k, tb=True) * sm_scale
        if need_mask:
            rows = (qi * block_q
                    + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
            cols = (kj * block_k
                    + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
            mask = cols < kv_len
            if causal:
                mask = jnp.logical_and(mask, cols <= rows)
            p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        else:
            p = jnp.exp(s - lse)
        dp = _dot(do, v, tb=True)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_scr[...] = dq_scr[...] + _dot(ds, k)

    @pl.when(kj == n_kv - 1)
    def _fini():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                sm_scale, causal, block_q, block_k, n_q, need_mask):
    kj, qi = pl.program_id(1), pl.program_id(2)
    kv_len = len_ref[pl.program_id(0)]

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]
        lse, delta = lse_ref[0], delta_ref[0]
        s = _dot(q, k, tb=True) * sm_scale             # (bq, bk)
        if need_mask:
            rows = (qi * block_q
                    + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
            cols = (kj * block_k
                    + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
            mask = cols < kv_len
            if causal:
                mask = jnp.logical_and(mask, cols <= rows)
            p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        else:
            p = jnp.exp(s - lse)
        dv_scr[...] = dv_scr[...] + _dot(p.astype(do.dtype), do, ta=True)
        dp = _dot(do, v, tb=True)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_scr[...] = dk_scr[...] + _dot(ds, q, ta=True)

    @pl.when(qi == n_q - 1)
    def _fini():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, lens, do, sm_scale, causal, block_q, block_k,
         interpret, need_mask):
    bh, tq, d = q.shape
    tk = k.shape[1]
    n_q, n_kv = tq // block_q, tk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)             # (bh, tq, 1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_kv=n_kv,
                          need_mask=need_mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda b, i, j, lens: (b, i, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, i, j, lens: (b, j, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, i, j, lens: (b, j, 0)),
                pl.BlockSpec((1, block_q, d),
                             lambda b, i, j, lens: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, i, j, lens: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, i, j, lens: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda b, i, j, lens: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mx_flash_dq",
    )(lens, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          need_mask=need_mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_kv, n_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda b, j, i, lens: (b, i, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, j, i, lens: (b, j, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, j, i, lens: (b, j, 0)),
                pl.BlockSpec((1, block_q, d),
                             lambda b, j, i, lens: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, j, i, lens: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda b, j, i, lens: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d),
                             lambda b, j, i, lens: (b, j, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, j, i, lens: (b, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mx_flash_dkv",
    )(lens, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_core(q, k, v, lens, sm_scale, causal, block_q, block_k, interpret,
                need_mask, have_lengths):
    o, _ = _fwd(q, k, v, lens, sm_scale, causal, block_q, block_k, interpret,
                need_mask, have_lengths)
    return o


def _flash_core_fwd(q, k, v, lens, sm_scale, causal, block_q, block_k,
                    interpret, need_mask, have_lengths):
    o, lse = _fwd(q, k, v, lens, sm_scale, causal, block_q, block_k,
                  interpret, need_mask, have_lengths)
    return o, (q, k, v, o, lse, lens)


def _flash_core_bwd(sm_scale, causal, block_q, block_k, interpret, need_mask,
                    have_lengths, res, do):
    q, k, v, o, lse, lens = res
    dq, dk, dv = _bwd(q, k, v, o, lse, lens, do, sm_scale, causal,
                      block_q, block_k, interpret, need_mask)
    import numpy as onp

    dlens = onp.zeros(lens.shape, jax.dtypes.float0)
    return dq, dk, dv, dlens


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# attention matrices up to this many bytes take the XLA path under
# impl="auto" — XLA's own fusion pipeline is flash-like and measured
# faster than the pallas kernel on-chip (T=4096 f32: ~12 ms vs ~15 ms;
# T=16384: ~76 ms vs ~3.3 s); past the cliff XLA fails to compile the
# T² buffer (T=32768 f32 → 34 GB) and the streaming pallas kernel is
# the only option.
_XLA_ATTN_BYTES_LIMIT = 2 << 30


def _xla_attention(q, k, v, lengths, causal, sm_scale, layout="bhtd"):
    """Same semantics as the pallas kernel, expressed as plain jnp ops —
    XLA fuses the softmax(QKᵀ)V pipeline itself.

    `layout="bthd"` contracts directly from the projection layout
    (batch, seq, heads, head_dim) — the head/seq "transpose" folds into
    the dot_general instead of materializing a relayout copy of the
    (B, T, C)-sized tensor (measured ~13 ms/step of `copy` ops in the
    seq-512 BERT profile with explicit transposes)."""
    if layout == "bthd":
        b, tq, h, d = q.shape
        tk = k.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm_scale
        neg = jnp.asarray(jnp.finfo(s.dtype).min / 2, s.dtype)
        if causal:
            mask = jnp.tril(jnp.ones((tq, tk), bool))
            s = jnp.where(mask, s, neg)
        if lengths is not None:
            lens = jnp.asarray(lengths, jnp.int32).reshape(b)
            kmask = jnp.arange(tk)[None, :] < lens[:, None]
            s = jnp.where(kmask[:, None, None, :], s, neg)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        if lengths is not None:
            qmask = jnp.arange(tq)[None, :] < lens[:, None]
            o = jnp.where(qmask[:, :, None, None], o, 0.0)
        return o
    b, h, tq, d = q.shape
    tk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    # finite mask constant in the score dtype: -1e30 would overflow f16/
    # bf16 to -inf and give NaN softmax rows (and NaN grads) on padded
    # sequences — same finite-NEG_INF discipline as the pallas kernel
    neg = jnp.asarray(jnp.finfo(s.dtype).min / 2, s.dtype)
    if causal:
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        s = jnp.where(mask, s, neg)
    if lengths is not None:
        lens = jnp.asarray(lengths, jnp.int32).reshape(b)
        kmask = jnp.arange(tk)[None, :] < lens[:, None]      # (B, Tk)
        s = jnp.where(kmask[:, None, None, :], s, neg)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    if lengths is not None:
        qmask = jnp.arange(tq)[None, :] < lens[:, None]      # (B, Tq)
        o = jnp.where(qmask[:, None, :, None], o, 0.0)
    return o


def flash_attention(q, k, v, lengths=None, causal=False, sm_scale=None,
                    block_q=512, block_k=512, interpret=None, impl="auto",
                    layout="bhtd"):
    """Fused scaled-dot-product attention.

    - `layout`: "bhtd" (B, H, T, D) or "bthd" (B, T, H, D — the natural
      output of a fused qkv projection; the XLA path contracts it
      directly so no head transpose is ever materialized, and the
      output comes back in (B, T, H, D) ready to collapse to (B, T, C)).
    - `lengths`: optional (B,) int32 valid sequence lengths (key padding AND
      query-row masking, self-attention semantics — the flash replacement
      for `npx.masked_softmax` with a valid_length mask).
    - `causal`: lower-triangular masking for decoder/LM use.
    - `impl`: "auto" picks the XLA-fused path while the T² attention
      matrix fits (see `_XLA_ATTN_BYTES_LIMIT`) and the O(T)-memory
      pallas streaming kernel beyond; "xla"/"pallas" force a path.
    - Differentiable on both paths (pallas via custom_vjp backward
      kernels, XLA via ordinary autodiff of the fused graph).
    """
    if layout == "bthd":
        b, t_q, h, d = q.shape
        t_k = k.shape[1]
    else:
        b, h, t_q, d = q.shape
        t_k = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if impl == "auto":
        attn_bytes = b * h * t_q * t_k * jnp.dtype(q.dtype).itemsize
        impl = "xla" if attn_bytes <= _XLA_ATTN_BYTES_LIMIT else "pallas"
    if impl not in ("xla", "pallas"):
        raise ValueError(f"flash_attention: unknown impl {impl!r}")
    _dispatch.note("flash_attention", impl)
    if impl == "xla":
        return _xla_attention(q, k, v, lengths, bool(causal),
                              float(sm_scale), layout=layout)
    if layout == "bthd":
        # the streaming kernel wants heads-major blocks; one relayout is
        # noise next to the O(T²) compute that forces the pallas path
        q = q.transpose(0, 2, 1, 3)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    tq = t_q
    tk = t_k
    if interpret is None:
        interpret = _dispatch.interpret_default()

    block_q = min(block_q, _round_up(tq, 8))
    block_k = min(block_k, _round_up(tk, 8))
    tq_pad = _round_up(tq, block_q)
    tk_pad = _round_up(tk, block_k)

    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    if tq_pad != tq:
        qr = jnp.pad(qr, ((0, 0), (0, tq_pad - tq), (0, 0)))
    if tk_pad != tk:
        kr = jnp.pad(kr, ((0, 0), (0, tk_pad - tk), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, tk_pad - tk), (0, 0)))

    if lengths is None:
        lens = jnp.full((b,), tk, jnp.int32)
    else:
        lens = jnp.asarray(lengths, jnp.int32).reshape(b)
    lens = jnp.repeat(lens, h)                         # (BH,)

    need_mask = bool(causal) or lengths is not None or tk_pad != tk
    o = _flash_core(qr, kr, vr, lens, float(sm_scale), bool(causal),
                    int(block_q), int(block_k), bool(interpret),
                    need_mask, lengths is not None)
    o = o[:, :tq].reshape(b, h, tq, d)
    return o.transpose(0, 2, 1, 3) if layout == "bthd" else o


def mha_flash(q, k, v, lengths=None, causal=False, sm_scale=None):
    """(B*H, T, D)-layout convenience wrapper matching `npx.batch_dot`
    attention code: caller flattens heads; lengths must already be per
    (B*H) row or None."""
    bh, t, d = q.shape
    o = flash_attention(q[:, None], k[:, None], v[:, None],
                        lengths=lengths, causal=causal, sm_scale=sm_scale)
    return o[:, 0]
