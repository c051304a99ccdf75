"""A dropless expert layer for serving: sigmoid routing over ALL the routed
experts, and the part of the routed sum that the experts HELD here give.

``route(u, w_router, top_k, scale, bias=None)``: ``s = sigmoid(u W_r)`` in
float32 over every routed expert, the `top_k` largest chosen — of ``s + bias``
where a score-correction bias is given, which enters the choice alone —, ``w =
scale * s_chosen / (sum s_chosen + 1e-20)``. ``held_experts(u, ids, weights,
experts, held, valid)``: ``sum_e w_e E_e(u)`` over a token's chosen experts
that lie in ``held = (first, count)``; ``E_e`` is told by `experts`: three
matrices an expert ``(W_gate, W_up, W_down)`` a gated SiLU, two ``(W_1, W_2)``
the ungated ``relu(x W_1)^2 W_2``; what the others would add is
left out (an expert-parallel deployment's one chip, before the exchange).
**Dropless**: there is no capacity. Every chosen (token, expert) pair whose
expert is held is computed, however the tokens fall — the buffer is sized for
all ``T * top_k`` pairs falling on held experts. (`parallel/moe.py` is the
training layer: top-1/top-2 under a capacity factor that drops tokens,
another mathematics.)

How the held pairs are computed:

1. **sort by expert into tiles.** Each held expert's pairs are laid into a
   buffer in whole tiles of `tile` rows (``_layout``): a tile belongs to ONE
   expert, so a grouped product is a tiled matmul whose weight block is
   named by the tile (``tile_expert``). Buffer rows: ``T * top_k`` rounded
   up to tiles, plus one tile of padding an expert held. Only the first
   ``n_tiles`` tiles are live.
2. **the grouped product** ``silu(x W_gate[e]) * (x W_up[e])`` (or ``relu(x
   W_1[e])^2``) then ``h W_down[e]`` over the live tiles: on one TPU device a
   pallas kernel (two launches: the first product with its activation fused,
   then the second) whose weight `BlockSpec`s read
   ``tile_expert`` from scalar memory — an expert no pair chose is never
   fetched, and the grid ends at the last live tile. Its name in the device
   trace is the kind of step's that launched it (`KERNEL_NAMES`:
   ``mx_moe_experts`` in a decode step, ``mx_moe_chunk_experts`` in a
   prefill chunk), which the caller says (`step`); how many rows a tile has
   is this module's own choice and names nothing. Elsewhere (the CPU, a
   multi-device mesh) the same tiles through one einsum over gathered
   weights. Counted as
   ``mx_kernel_dispatch_total{op="moe_experts",impl=}``.
3. **the weighted scatter back**: each token gathers its held pairs' rows
   and adds them under their weights (a gather, so that rows of dead tiles —
   never written — are never read).

``stats`` is int32 ``(held pairs, distinct held experts hit)``, for the
program's counters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch

__all__ = ["route", "held_experts", "grouped_ffn"]

_VMEM_LIMIT = 48 * 2 ** 20      # of the v5e's 128 MiB; the default is 16


def route(u, w_router, top_k, scale, bias=None):
    """``(ids (T, top_k) int32, weights (T, top_k) float32)``: sigmoid
    scores in float32 at the highest matmul precision (a rounded score is
    another choice of experts), no group limit. `bias` (E,): a
    score-correction term added for the CHOICE only; the weights are the
    chosen experts' own scores."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32),
                                      w_router.astype(jnp.float32)))
    if bias is None:
        chosen, ids = jax.lax.top_k(s, top_k)
    else:
        _, ids = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        chosen = jnp.take_along_axis(s, ids, axis=-1)
    w = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w


def _tile_rows(n_pairs, routed):
    """Rows a tile, by the rows an expert can expect of a step's `n_pairs`
    pairs over `routed` experts (an expert's rows are padded to whole
    tiles): few where that is under 4 (a decode step: 64 tokens x 8 of 256
    experts, 64 x 22 of 512), a full MXU pass where it is more (a prefill
    chunk)."""
    return 16 if n_pairs < 4 * routed else 128


#: the kernel's name in the device trace, by the kind of step that launches
#: it (the caller's `step`): a metric of the decode step reads the first,
#: whatever the step's batch and so its tile
KERNEL_NAMES = {"decode": "mx_moe_experts", "chunk": "mx_moe_chunk_experts"}


def _layout(ids, ok, held, tile):
    """Where each held pair goes. ``ids`` (T, K), ``ok`` (T, K) bool (the
    pair's expert is held and its token real). Returns ``(dest (T, K) row of
    the buffer (0 where not ok), src (M,) token of each buffer row (0 for
    padding), tile_expert (M / tile,) local expert of each tile, n_tiles
    live tiles, counts (held,))``."""
    first, count = held
    t, k = ids.shape
    m_tiles = -(-t * k // tile) + count
    local = jnp.where(ok, ids - first, count).reshape(-1)         # (T K,)
    counts = jnp.zeros(count + 1, jnp.int32).at[local].add(1)[:count]
    tiles_of = -(-counts // tile)
    tile_end = jnp.cumsum(tiles_of)
    row0 = (tile_end - tiles_of) * tile          # an expert's first row
    # a pair's rank among its expert's pairs, in token order
    order = jnp.argsort(local, stable=True)
    sorted_local = local[order]
    start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(counts)])[:count + 1]
    rank_sorted = jnp.arange(t * k, dtype=jnp.int32) \
        - start[jnp.minimum(sorted_local, count)]
    dest_sorted = jnp.where(
        sorted_local < count,
        row0[jnp.minimum(sorted_local, count - 1)] + rank_sorted, 0)
    dest = jnp.zeros(t * k, jnp.int32).at[order].set(dest_sorted)
    rows = m_tiles * tile
    # buffer row -> token; rows no pair fills read token 0 and are never
    # gathered back
    src = jnp.zeros(rows, jnp.int32).at[
        jnp.where(sorted_local < count, dest_sorted, rows)].set(
            (order // k).astype(jnp.int32), mode="drop")
    # a dead tile names the last live tile's expert
    n_tiles = tile_end[-1]
    tile_expert = jnp.searchsorted(
        tile_end, jnp.minimum(jnp.arange(m_tiles, dtype=jnp.int32),
                              jnp.maximum(n_tiles, 1) - 1),
        side="right").astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, count - 1)
    return dest.reshape(t, k), src, tile_expert, n_tiles, counts


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------

def _relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def _xla_grouped_ffn(x, tile_expert, ws, tile):
    n = x.shape[0] // tile
    xt = x.reshape(n, tile, -1)
    f32 = jnp.float32
    first = [jnp.einsum("nmk,nkf->nmf", xt, w[tile_expert],
                        preferred_element_type=f32) for w in ws[:-1]]
    h = jax.nn.silu(first[0]) * first[1] if len(first) == 2 \
        else _relu2(first[0])
    return jnp.einsum("nmf,nfc->nmc", h.astype(x.dtype), ws[-1][tile_expert],
                      preferred_element_type=f32).reshape(x.shape[0], -1)


def _kernel(n_ref, _te_ref, x_ref, *refs, act):
    """One (tile, out block, in block) step of a tile's matmul; `act`:
    ``"silu_gated"`` — two weights, and ``silu(x W0) * (x W1)`` goes out —,
    ``"relu2"`` — ``relu(x W0)^2`` —, or None, the product as it is."""
    gated = act == "silu_gated"
    n_w = 2 if gated else 1
    w_refs, o_ref, acc = refs[:n_w], refs[n_w], refs[n_w + 1:]
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(i < n_ref[0])          # no live tile at all: the grid's one step
    def _():
        @pl.when(kk == 0)
        def _():
            for a in acc:
                a[...] = jnp.zeros_like(a)

        x = x_ref[...]
        for a, w in zip(acc, w_refs):
            a[...] += jnp.dot(x, w[...], preferred_element_type=jnp.float32)

        @pl.when(kk == pl.num_programs(2) - 1)
        def _():
            y = acc[0][...]
            if gated:
                y = jax.nn.silu(y) * acc[1][...]
            elif act == "relu2":
                y = _relu2(y)
            o_ref[...] = y.astype(o_ref.dtype)


def _block(n, most):
    """The largest multiple of 128 that divides `n` and is at most `most`
    (`n` itself where it has none: a tiny width in the tests)."""
    best = [b for b in range(128, min(n, most) + 1, 128) if n % b == 0]
    return best[-1] if best else n


def _pallas_grouped(x, tile_expert, n_tiles, ws, tile, out_dtype, name,
                    interpret, act=None):
    """``x`` (M, K) through ``ws`` (1 or 2 of (E, K, N)) tile by tile:
    (M, N), ``silu(x W0) * (x W1)`` where two are given, else `act` of
    ``x W0``."""
    m, k = x.shape
    n = ws[0].shape[2]
    tk, tn = _block(k, 1024), _block(n, 1024)
    if len(ws) == 2:
        act = "silu_gated"
    w_spec = pl.BlockSpec((None, tk, tn),
                          lambda i, j, kk, n_ref, te: (te[i], kk, j))
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # as far as the last live tile (one step where there is none)
            grid=(jnp.maximum(n_tiles, 1), n // tn, k // tk),
            in_specs=[pl.BlockSpec((tile, tk),
                                   lambda i, j, kk, n_ref, te: (i, kk))]
            + [w_spec] * len(ws),
            out_specs=pl.BlockSpec((tile, tn),
                                   lambda i, j, kk, n_ref, te: (i, j)),
            scratch_shapes=[pltpu.VMEM((tile, tn), jnp.float32)] * len(ws)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(jnp.reshape(n_tiles, (1,)).astype(jnp.int32), tile_expert, x, *ws)


@functools.partial(jax.jit, static_argnames=("tile", "name", "interpret"))
def _pallas_grouped_ffn(x, tile_expert, n_tiles, w_gate, w_up, w_down, tile,
                        name, interpret):
    h = _pallas_grouped(x, tile_expert, n_tiles, (w_gate, w_up), tile,
                        x.dtype, name, interpret)
    return _pallas_grouped(h, tile_expert, n_tiles, (w_down,), tile,
                           jnp.float32, name, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "name", "interpret"))
def _pallas_grouped_relu2(x, tile_expert, n_tiles, w_1, w_2, tile, name,
                          interpret):
    h = _pallas_grouped(x, tile_expert, n_tiles, (w_1,), tile, x.dtype, name,
                        interpret, act="relu2")
    return _pallas_grouped(h, tile_expert, n_tiles, (w_2,), tile,
                           jnp.float32, name, interpret)


def grouped_ffn(x, tile_expert, n_tiles, *ws, tile, step="decode", impl=None):
    """Rows ``x`` (M, C), a tile of `tile` rows an expert (``tile_expert``),
    through that expert's layer — `ws` ``(W_gate, W_up, W_down)`` a gated
    SiLU, ``(W_1, W_2)`` the ungated ``relu^2`` —: (M, C) float32. Rows of
    tiles past ``n_tiles`` hold anything. `step`: the kind of step that
    launches it (a key of `KERNEL_NAMES`). `impl`: ``"pallas"`` / ``"xla"``
    (tests); None chooses from what the process observes."""
    if impl is None:
        impl = "pallas" if _dispatch.use_pallas() else "xla"
        _dispatch.note("moe_experts", impl)
    if impl == "pallas":
        fn = _pallas_grouped_ffn if len(ws) == 3 else _pallas_grouped_relu2
        return fn(x, tile_expert, n_tiles, *ws, tile, KERNEL_NAMES[step],
                  _dispatch.interpret_default())
    return _xla_grouped_ffn(x, tile_expert, ws, tile)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

def held_experts(u, ids, weights, experts, held, valid=None, step="decode",
                 impl=None, routed=None):
    """``(sum over a token's chosen AND held experts of w_e E_e(u) (T, C)
    float32, stats)`` — see the module docstring. ``u`` (T, C) in the
    experts' dtype; ``experts = (W_gate (held, C, F), W_up, W_down (held, F,
    C))`` or ``(W_1 (held, C, F), W_2 (held, F, C))``; `valid` (T,) bool: a
    row that is not chooses nothing; `step`: ``"decode"`` or ``"chunk"``,
    the kind of step this is a part of (it names the kernel in the device
    trace); `routed`: the experts the router chose among (the tile follows
    the rows one of them can expect; None: the held ones are all)."""
    first, count = held
    t, k = ids.shape
    ok = (ids >= first) & (ids < first + count)
    if valid is not None:
        ok = ok & valid[:, None]
    tile = _tile_rows(t * k, count if routed is None else routed)
    dest, src, tile_expert, n_tiles, counts = _layout(ids, ok, held, tile)
    y = grouped_ffn(u[src], tile_expert, n_tiles, *experts, tile=tile,
                    step=step, impl=impl)
    picked = jnp.where(ok[..., None], y[dest], 0.0)               # (T, K, C)
    out = jnp.einsum("tk,tkc->tc", jnp.where(ok, weights, 0.0), picked)
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0)]).astype(jnp.int32)
    return out, stats
