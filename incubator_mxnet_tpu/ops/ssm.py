"""The selective state-space recurrence of a Mamba-2 layer, for serving: one
token a slot against a resident state, and a prefill chunk in the chunked
(SSD) form with the state handed in and out.

Per head ``h`` (its group ``g(h) = h // (H / G)`` shares ``B`` and ``C``), with
``dt`` already ``softplus(raw + bias)``, ``A`` negative, everything float32::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t         # S: (P, N)
    y_t = S_t C_t + D x_t

``ssm_decode(state, x, B, C, dt, A, D, active)``: one step for every slot,
``state`` a slot's ``(H, P, N)`` AS STORED (`state_store_shape`: ``n`` in the
sublanes, the ``p`` of two 64-wide heads side by side in the lanes); a slot
that is not `active` keeps its state bit for bit. On one TPU device a pallas
kernel, ``mx_ssm_decode`` in the device trace: a slot's whole state is one
block, read once, updated and written back IN PLACE
(``input_output_aliases``); ``dt x``, the decay and ``y`` are rows of the
natural ``(H, P)`` layout, broadcast over and summed over sublanes, and a
group's ``B`` and ``C`` are broadcast over the lanes once for its 16 heads.
(The first draft kept ``(P, N)`` planes: 17 cross-lane permutes and
reductions a head, 45 % of the HBM roofline on the chip; PERF.md §6, PR 35.)
Elsewhere (the CPU, a multi-device mesh) the same in ``jax.numpy``. Counted
as ``mx_kernel_dispatch_total{op="ssm_decode",impl=}``.

``ssm_chunk(state_in, x, B, C, dt, A, D, valid)``: ``T`` rows of one slot in
blocks of `block` rows (the source's ``chunk_size``): inside a block the
products ``(C_t . B_s) exp(cum_t - cum_s) dt_s x_s`` (``cum`` the running sum of
``dt A``), between blocks the state's own recurrence, the state entering a
block read through ``C_t exp(cum_t)``. Rows that are not `valid` (a bucket's
padding) have ``dt = 0``: they decay nothing and add nothing, so the state
handed out is the state after the last real row. XLA matmuls at ``highest``
precision (the state and its update are float32 whatever the weights are).

The causal depthwise convolution before the recurrence keeps a tail of
``kernel - 1`` rows a slot (`conv_decode`, `conv_chunk`): the same carried
state, in the small.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch

__all__ = ["ssm_decode", "ssm_chunk", "conv_decode", "conv_chunk",
           "state_store_shape", "pack_state", "unpack_state", "KERNEL_NAME"]

KERNEL_NAME = "mx_ssm_decode"
LANES = 128
_VMEM_LIMIT = 48 * 2 ** 20      # of the v5e's 128 MiB; the default is 16
_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# the state as it is stored
# ---------------------------------------------------------------------------

def heads_packed(heads, p, groups):
    """Heads side by side in a stored row's lanes: the most that divide a
    group's heads (they share ``B`` and ``C``) and fit 128 lanes."""
    rep = heads // groups
    return max((k for k in range(1, rep + 1)
                if rep % k == 0 and k * p <= LANES), default=1)


def state_store_shape(heads, p, n, groups):
    """A slot's state as stored: ``(H / k, N, k P)``, ``k =
    heads_packed(...)``. The state index ``n`` lies in the sublanes and ``k``
    heads' ``p`` side by side in the lanes (64-wide heads: two to a row of
    128, nothing padded), so that a head's ``x`` row and ``y`` row are
    lane-dense rows of the natural ``(H, P)`` layout, broadcast over and
    reduced over SUBLANES — vector adds, where a ``(P, N)`` plane needs a
    cross-lane permute a vreg to bring ``x`` in and a cross-lane reduction a
    vreg to bring ``y`` out."""
    k = heads_packed(heads, p, groups)
    return heads // k, n, k * p


def pack_state(state, groups):
    """``(..., H, P, N)`` -> as stored ``(..., H / k, N, k P)``."""
    *lead, heads, p, n = state.shape
    k = heads_packed(heads, p, groups)
    t = state.reshape(*lead, heads // k, k, p, n)
    return jnp.moveaxis(t, -1, -3).reshape(*lead, heads // k, n, k * p)


def unpack_state(stored, p):
    """As stored -> ``(..., H, P, N)``."""
    *lead, rows, n, lanes = stored.shape
    k = lanes // p
    t = stored.reshape(*lead, rows, n, k, p)
    return jnp.moveaxis(t, -3, -1).reshape(*lead, rows * k, p, n)


# ---------------------------------------------------------------------------
# one token a slot
# ---------------------------------------------------------------------------

def _xla_decode(state, x, B, C, dt, A, D, active):
    groups, p = B.shape[1], x.shape[-1]
    rep = x.shape[1] // groups
    s = unpack_state(state, p)
    decay = jnp.exp(dt * A)                                       # (S, H)
    Bh, Ch = (jnp.repeat(t, rep, axis=1) for t in (B, C))        # (S, H, N)
    new = decay[..., None, None] * s \
        + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    y = jnp.sum(new * Ch[:, :, None, :], axis=-1) + D[None, :, None] * x
    return y, jnp.where(active[:, None, None, None],
                        pack_state(new, groups), state)


def _decode_kernel(act_ref, s_ref, xdt_ref, dec_ref, b_ref, c_ref, o_ref,
                   y_ref, *, groups):
    """One slot: every stored row's ``(N, k P)`` plane of the state updated
    and read against its group's ``C``. A group's ``B`` and ``C`` columns
    are broadcast over the lanes once for its rows."""
    s = pl.program_id(0)
    rows, n, lanes = s_ref.shape[1:]
    per_group = rows // groups

    @pl.when(act_ref[s] != 0)
    def _():
        for g in range(groups):
            bb = jnp.broadcast_to(b_ref[0, :, g:g + 1], (n, lanes))
            cb = jnp.broadcast_to(c_ref[0, :, g:g + 1], (n, lanes))
            for r in range(g * per_group, (g + 1) * per_group):
                new = dec_ref[0, r:r + 1, :] * s_ref[0, r] \
                    + bb * xdt_ref[0, r:r + 1, :]                # (N, k P)
                o_ref[0, r] = new
                y_ref[0, r:r + 1, :] = jnp.sum(new * cb, axis=0,
                                               keepdims=True)

    @pl.when(act_ref[s] == 0)
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_decode(state, x, B, C, dt, A, D, active, interpret):
    n_slots, rows, n, lanes = state.shape
    heads, p = x.shape[1:]
    groups = B.shape[1]
    f32 = jnp.float32
    # a stored row's heads side by side, as the state's lanes are: the
    # natural (H, P) layout, regrouped; the decay repeated over a head's p
    xdt = (dt[..., None] * x).reshape(n_slots, rows, lanes)
    decay = jnp.repeat(jnp.exp(dt * A), p, axis=1).reshape(
        n_slots, rows, lanes)
    slot = lambda *shape: pl.BlockSpec(                  # noqa: E731
        (1,) + shape, lambda s, act: (s,) + (0,) * len(shape))
    new, y = pl.pallas_call(
        functools.partial(_decode_kernel, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_slots,),
            in_specs=[slot(rows, n, lanes), slot(rows, lanes),
                      slot(rows, lanes), slot(n, groups), slot(n, groups)],
            out_specs=[slot(rows, n, lanes), slot(rows, lanes)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((n_slots, rows, lanes), f32)],
        # the state (operand 1, after the prefetched `active`) is updated
        # in place
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_NAME,
    )(active.astype(jnp.int32), state, xdt, decay,
      jnp.swapaxes(B, 1, 2), jnp.swapaxes(C, 1, 2))
    return y.reshape(n_slots, heads, p) + D[None, :, None] * x, new


def ssm_decode(state, x, B, C, dt, A, D, active, impl=None):
    """One step of every slot. ``state`` (S,) + `state_store_shape`, ``x``
    (S, H, P), ``B`` / ``C`` (S, G, N), ``dt`` (S, H) (after the softplus),
    ``A`` / ``D`` (H,), all float32; ``active`` (S,) bool. Returns ``(y (S,
    H, P), state')``; an inactive slot's state is returned as it was (its
    ``y`` is not to be used). `impl`: ``"pallas"`` / ``"xla"`` (tests); None
    chooses from what the process observes."""
    if impl is None:
        impl = "pallas" if _dispatch.use_pallas() else "xla"
        _dispatch.note("ssm_decode", impl)
    if impl == "pallas":
        return _pallas_decode(state, x, B, C, dt, A, D, active,
                              _dispatch.interpret_default())
    return _xla_decode(state, x, B, C, dt, A, D, active)


# ---------------------------------------------------------------------------
# a prefill chunk
# ---------------------------------------------------------------------------

def ssm_chunk(state_in, x, B, C, dt, A, D, valid, block=128):
    """``T`` rows of one slot, chunked. ``state_in`` as stored
    (`state_store_shape`), ``x`` (T, H, P), ``B`` / ``C`` (T, G, N), ``dt``
    (T, H), ``A`` / ``D`` (H,), float32; ``valid`` (T,) bool, true for a
    prefix of the rows. ``T`` is a multiple of `block` or smaller than it.
    Returns ``(y (T, H, P), state_out)``, the state as stored."""
    t, heads, p = x.shape
    groups, n = B.shape[1:]
    rep = heads // groups
    ln = min(block, t)
    if t % ln:
        raise ValueError(f"{t} rows are not whole blocks of {ln}")
    nb = t // ln
    f32 = jnp.float32
    dt = jnp.where(valid[:, None], dt, 0.0).astype(f32)
    cum = jnp.cumsum((dt * A).reshape(nb, ln, groups, rep), axis=1)
    xd = (x * dt[..., None]).reshape(nb, ln, groups, rep, p)
    Bb, Cb = B.reshape(nb, ln, groups, n), C.reshape(nb, ln, groups, n)
    # inside a block: (C_t . B_s) exp(cum_t - cum_s) for s <= t
    cb = jnp.einsum("btgn,bsgn->bgts", Cb, Bb, precision=_HI)
    seg = cum[:, :, None] - cum[:, None, :]                # (nb, t, s, G, R)
    seen = jnp.tril(jnp.ones((ln, ln), bool))[None, :, :, None, None]
    m = jnp.exp(jnp.where(seen, seg, -jnp.inf))
    m = jnp.transpose(m, (0, 3, 4, 1, 2)) * cb[:, :, None]  # (nb,G,R,t,s)
    y = jnp.einsum("bgrts,bsgrp->btgrp", m, xd, precision=_HI)
    # what a block adds to the state, and the state entering each block
    to_end = jnp.exp(cum[:, -1:] - cum)                       # (nb, s, G, R)
    added = jnp.einsum("bsgrp,bsgn->bgrpn", xd * to_end[..., None], Bb,
                       precision=_HI)
    whole = jnp.exp(cum[:, -1])                                # (nb, G, R)

    def step(s, blk):
        add, dec = blk
        return dec[..., None, None] * s + add, s

    out, entering = jax.lax.scan(
        step, unpack_state(state_in, p).reshape(groups, rep, p, n).astype(f32),
        (added, whole))
    y = y + jnp.einsum("btgn,bgrpn->btgrp", Cb, entering, precision=_HI) \
        * jnp.exp(cum)[..., None]
    return y.reshape(t, heads, p) + D[None, :, None] * x, \
        pack_state(out.reshape(heads, p, n), groups)


# ---------------------------------------------------------------------------
# the causal depthwise convolution and its tail
# ---------------------------------------------------------------------------

def conv_decode(tail, row, w, b, active):
    """One new row a slot. ``tail`` (S, K - 1, C) the rows before it (any
    float dtype), ``row`` (S, C) float32, ``w`` (K, C) — ``w[K - 1]`` weighs
    the new row —, ``b`` (C,). Returns ``(out (S, C) float32 before the
    activation, tail')``; an inactive slot keeps its tail."""
    win = jnp.concatenate([tail.astype(jnp.float32), row[:, None]], axis=1)
    out = b + jnp.sum(win * w[None], axis=1)
    return out, jnp.where(active[:, None, None],
                          win[:, 1:].astype(tail.dtype), tail)


def conv_chunk(tail, rows, w, b, t_len):
    """``T`` rows of one slot. ``tail`` (K - 1, C), ``rows`` (T, C) float32,
    `t_len` the real rows (traced). Returns ``(out (T, C), tail')``: the
    tail handed out is the last ``K - 1`` rows before row `t_len`."""
    k, t = w.shape[0], rows.shape[0]
    ext = jnp.concatenate([tail.astype(jnp.float32), rows], axis=0)
    out = b + sum(w[j] * ext[j:j + t] for j in range(k))
    new = jax.lax.dynamic_slice_in_dim(ext, t_len, k - 1, axis=0)
    return out, new.astype(tail.dtype)
