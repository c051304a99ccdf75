"""Single-token attention over a paged KV pool (the decode step of `mx.serve`).

``paged_decode_attention(q, k_pool, v_pool, table, lengths)``: `q` is one
query row per slot ``(S, H, d)``; the pools are one layer's leaves as the
engine keeps them (see *a page as it is stored*); ``table`` ``(S, P)`` int32
maps a slot's token range to pool pages; ``lengths`` ``(S,)`` int32 is how
many tokens of the slot are alive (``pos + 1`` for a decoding slot, 0 for a
free or prefilling one). Returns ``(S, H, d)``; a slot of length 0 yields
zeros. **Grouped heads**: where `q` has ``rep`` times the heads the pools
store, query head ``h`` attends stored head ``h // rep``; a page is fetched
once for all of them.

**A page as it is stored.** A page is ``(H, page_tokens, d)`` values. Where
the head is narrower than the TPU's 128 lanes, a float pool keeps each
head's ``(page_tokens, d)`` plane row-major as ``(page_tokens * d // 128,
128)`` — ``128 // d`` tokens side by side in a row (`page_store_shape`,
`pack_pages`, `unpack_pages`: plain reshapes). Stored ``(n_pages, H,
page_tokens, 64)``, a float32 leaf is padded to 128 lanes, twice its bytes,
and the TPU lays it out with the PAGE index in the lanes to win the padding
back: a page is then no contiguous block, every write and gather turns the
whole leaf to a page-major layout and back, and no DMA can name a page.
(Pinning a row-major layout on the leaf instead does not survive jax's
persistent compilation cache, which hands back executables compiled for
another layout.) Packed, the leaf has no padding and one layout, row-major,
in which ``pool[page]`` is one contiguous block. int8 pools are not packed.

Two implementations of the same semantics, chosen from what the process can
observe (`_dispatch.use_pallas()` and the pool's dtype), never from a
failure or a knob, and counted as
``mx_kernel_dispatch_total{op="paged_decode_attention",impl=}``:

- **pallas** (`mx_paged_decode`; one TPU device, float pools): lengths and
  a work list made from the table (`_work_list`) are scalar-prefetched and
  the pools stay in HBM. The grid has one step per LIVE block of
  `_BLOCK_PAGES` pages, slot after slot (its length is read on the
  device), and each step has that many page-sized blocks of K and of V
  brought to VMEM by `BlockSpec`s whose index maps read the work list,
  double-buffered against the step before. A page past a slot's length is
  not fetched, nor the trash page, nor a free slot's row, and takes no
  part in the arithmetic. Online softmax in float32, one page at a time;
  products are exact float32 (the VPU multiplies, no MXU pass narrows
  them).
- **xla** (CPU, a multi-device mesh — GSPMD cannot partition a Mosaic
  kernel — and int8 pools, which dequantise by a per-(page, head) scale):
  the expression the engine has always had — gather every slot's whole
  ``P * page_tokens`` view through the table, mask, softmax, two einsums.

**Latent pages** (``mla_decode_attention``, the MLA family: `serve/mla.py`). A
page is ONE leaf of ``(page_tokens, W)`` rows ``[c_kv (rank) ; k_rope ;
zeros]`` shared by every head, ``W`` the row's ``rank + rope`` values rounded
up to whole 128-lane tiles (`latent_store_width`: 576 -> 640; a bfloat16 row
of 576 would be padded to 640 lanes by the chip's tiling anyway, so a row
takes 1,280 B either way, and stored so a page is one contiguous block and a
row's score is one product). The queries come *absorbed*, ``(S, H, W)``, and
what goes back is the weighted sum of ``c_kv``, ``(S, H, rank)``. The kernel
``mx_mla_decode`` has a grid of its own (`_mla_work_list`), a step a live
block of `_MLA_BLOCK_PAGES` pages, slot after slot, and fetches a block's
pages ITSELF: the pool is one operand left in HBM, the table is
scalar-prefetched flat, and a step starts one copy a live page of a LATER
step's block into one of `_MLA_BUFFERS` ``(G pt, W)`` VMEM blocks (none for
a page past the slot's length), then waits for its own, started that many
steps less one earlier. (An operand a page, each pipelined through its own
`BlockSpec`, cost a grid step more scalar bookkeeping than the products
took: `tools/kernel_schedule.py`.) The step's body is written once a block,
for the compiler orders loads after every copy into the same array; the
copies start after the first product in program order and their address
arithmetic lies under the products. They carry no range checks
(`disable_bounds_checks` on this one call): the table is clipped to the pool
before the call. Rows never fetched hold zeros or an earlier page (every
block is zeroed at the call's first step), and the mask gives them no
weight. A step is two MXU products, ``(H, W) x (W, rows)`` for the scores
and ``(H, rows) x (rows, rank)`` for the sum, bfloat16 into float32, with
the online softmax between them in float32; a slot's last block divides and
stores the output row. At 128 heads that is 2 x 128 x (576 + 512) operations
a row of 1,152 useful bytes, 242 op/B: on the v5e's ridge. The XLA
expression (the CPU, a mesh) gathers every slot's view.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch

__all__ = ["paged_decode_attention", "takes_kernel", "page_store_shape",
           "pack_pages", "unpack_pages", "mla_decode_attention",
           "latent_store_width"]

NEG_INF = -1.0e30   # finite stand-in for -inf: exp() and max() stay NaN-free
LANES = 128
_BLOCK_PAGES = 8    # pages of K and of V per grid step, at most


def takes_kernel(pool_dtype):
    """True where `paged_decode_attention` takes the pallas kernel for a
    pool of this dtype, under the mesh active now."""
    return (_dispatch.use_pallas()
            and jnp.issubdtype(pool_dtype, jnp.floating))


# ---------------------------------------------------------------------------
# a page as it is stored
# ---------------------------------------------------------------------------

def page_store_shape(page_tokens, d):
    """``(rows, lanes)`` of one head's plane of a float page: packed to
    128 lanes where whole tokens fit a row and whole rows a page, else
    ``(page_tokens, d)`` as it is."""
    if d < LANES and LANES % d == 0 and (page_tokens * d) % LANES == 0:
        return page_tokens * d // LANES, LANES
    return page_tokens, d


def pack_pages(x):
    """``(..., page_tokens, d)`` -> as stored (a reshape)."""
    return x.reshape(x.shape[:-2] + page_store_shape(*x.shape[-2:]))


def unpack_pages(x, d):
    """As stored -> ``(..., page_tokens, d)`` (a reshape)."""
    rows, lanes = x.shape[-2:]
    return x.reshape(x.shape[:-2] + (rows * lanes // d, d))


# ---------------------------------------------------------------------------
# the XLA expression
# ---------------------------------------------------------------------------

def _view(pool_l, scale_l, table, d):
    """Every slot's logical view ``(S, H, P * page_tokens, d)`` gathered
    through the table (int8 pools dequantise by their per-page scale)."""
    v = unpack_pages(jnp.take(pool_l, table, axis=0), d)   # (S, P, H, pt, d)
    if scale_l is not None:
        sc = jnp.take(scale_l, table, axis=0)
        v = v.astype(jnp.float32) * sc[..., None, None]
    S, P, H, pt, _ = v.shape
    return jnp.transpose(v, (0, 2, 1, 3, 4)).reshape(S, H, P * pt, d)


def _xla_paged_decode(q, k_pool, v_pool, table, lengths, k_scale, v_scale):
    d = q.shape[-1]
    vk = _view(k_pool, k_scale, table, d)
    vv = _view(v_pool, v_scale, table, d)
    if q.shape[1] != vk.shape[1]:       # grouped: every query head its own
        rep = q.shape[1] // vk.shape[1]
        vk, vv = (jnp.repeat(t, rep, axis=1) for t in (vk, vv))
    s = jnp.einsum("shqd,shkd->shqk", q[:, :, None, :], vk,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(d)
    mask = jnp.arange(vk.shape[2])[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(vv.dtype)
    o = jnp.einsum("shqk,shkd->shqd", p, vv)[:, :, 0, :]
    # a slot with nothing alive: softmax over an empty set is NaN; the
    # op's contract is zeros
    return jnp.where((lengths > 0)[:, None, None], o, jnp.zeros_like(o))


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _kernel(len_ref, slot_ref, block_ref, _page_ref, q_ref, *refs,
            block_pages, head_dim, sm_scale):
    G, d = block_pages, head_dim
    k_refs, v_refs = refs[:G], refs[G:2 * G]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * G:]
    i = pl.program_id(0)
    j = block_ref[i]                    # which block of its slot's pages
    H, rows, lanes = k_refs[0].shape
    rep = q_ref.shape[1]                # query heads a stored head
    per_row = lanes // d                # tokens side by side in a row
    length = len_ref[slot_ref[i]]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the query, once per token of a row: (H, rep, lanes)
    q = q_ref[...].astype(jnp.float32) * sm_scale
    row = jax.lax.broadcasted_iota(jnp.int32, (H, rows, 1), 1)
    if per_row > 1:                     # the lanes of a row's c-th token
        lane = jax.lax.broadcasted_iota(jnp.int32, (H, rows, lanes), 2)
        seg = [(lane >= c * d) & (lane < (c + 1) * d)
               for c in range(per_row)]

    for g in range(G):
        first = (j * G + g) * rows * per_row    # the page's first position

        # a page wholly past the length was not fetched (its buffer holds
        # whatever page came before): it takes no part
        @pl.when(first < length)
        def _():
            k = k_refs[g][...].astype(jnp.float32)        # (H, rows, lanes)
            v = v_refs[g][...].astype(jnp.float32)
            for r in range(rep):        # the stored head's r-th query head
                at = (Ellipsis,) if rep == 1 else (slice(None),
                                                   slice(r, r + 1))
                kq = k * (q if rep == 1 else q[at])
                sc = []                 # per token of a row: (H, rows, 1)
                for c in range(per_row):
                    part = kq if per_row == 1 else jnp.where(seg[c], kq, 0.0)
                    s_c = jnp.sum(part, axis=-1, keepdims=True)
                    alive = first + row * per_row + c < length
                    sc.append(jnp.where(alive, s_c, NEG_INF))
                m = m_scr[at]
                m_new = m
                for s_c in sc:
                    m_new = jnp.maximum(m_new,
                                        jnp.max(s_c, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)                # (H, 1, 1)
                p = [jnp.exp(s_c - m_new) for s_c in sc]  # masked: exp(-1e30)
                w = p[0]                # each token's weight on its lanes
                for c in range(1, per_row):
                    w = jnp.where(seg[c], p[c], w)
                m_scr[at] = m_new
                l_scr[at] = alpha * l_scr[at] + sum(
                    jnp.sum(p_c, axis=1, keepdims=True) for p_c in p)
                acc_scr[at] = alpha * acc_scr[at] + jnp.sum(
                    w * v, axis=1, keepdims=True)         # (H, 1, lanes)

    # the slot's row of the output stays in VMEM until the slot changes:
    # what its last block writes is what goes back. Still one partial sum
    # per token of a row; the caller adds them
    l = l_scr[...]
    o_ref[...] = (acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
                  ).astype(o_ref.dtype)


def _work_list(table, lengths, page_tokens, block_pages):
    """The grid, made from the table and the lengths (a few scalar-sized
    XLA ops, the same for every layer of a step): one grid step per LIVE
    block of `block_pages` pages, slot after slot — ``n`` of them (at least
    one), then ``slot[i]``, ``block[i]`` and, flat, ``page[i, g]``: the pool
    page operand `g` holds at step `i`. That is the slot's own page where
    it is alive, else the page the operand held the step before (the
    pipeline fetches a block only when its index changes), else — before
    the operand's first live page — that page, a prefetch. No dead page,
    no free slot's row and not the trash page is named while the operand
    has a live page anywhere."""
    S, P = table.shape
    G = block_pages
    NB = P // G
    n_pages = -(-lengths // page_tokens)                          # (S,)
    live_block = (jnp.arange(NB)[None, :] * G < n_pages[:, None]).reshape(-1)
    n = jnp.sum(live_block, dtype=jnp.int32)
    flat, = jnp.nonzero(live_block, size=S * NB, fill_value=0)
    flat = flat.astype(jnp.int32)
    slot, block = flat // NB, flat % NB
    pos = block[:, None] * G + jnp.arange(G, dtype=jnp.int32)[None, :]
    step = jnp.arange(S * NB, dtype=jnp.int32)[:, None]
    live = (pos < n_pages[slot][:, None]) & (step < n)
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    first = jnp.argmax(live, axis=0).astype(jnp.int32)
    src = jnp.where(last >= 0, last, first[None, :])
    page = jnp.take_along_axis(table[slot[:, None], pos], src, axis=0)
    return jnp.maximum(n, 1), slot, block, page.reshape(-1)


# jitted, so that a program calling it once a layer traces and lowers the
# kernel once (lowering 48 pallas calls one by one is seconds of set-up)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_paged_decode(q, k_pool, v_pool, table, lengths, interpret):
    S, Hq, d = q.shape
    n_pages, H, rows, lanes = k_pool.shape
    P = table.shape[1]
    if Hq % H or lanes % d or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pools {k_pool.shape} / {v_pool.shape} do not match q {q.shape}")
    rep = Hq // H                       # query heads a stored head
    per_row = lanes // d
    pt = rows * per_row
    # pages per grid step: one-page steps would cost more than the pages
    G = max(g for g in range(1, _BLOCK_PAGES + 1) if P % g == 0)
    table = table.astype(jnp.int32)
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, P * pt)
    n, slot, block, page = _work_list(table, lengths, pt, G)
    row = pl.BlockSpec((None, H, rep, lanes),
                       lambda i, lens, slot, *_: (slot[i], 0, 0, 0))
    pages = [pl.BlockSpec((None, H, rows, lanes),
                          lambda i, lens, slot, block, page, g=g:
                          (page[i * G + g], 0, 0, 0))
             for g in range(G)]
    out = pl.pallas_call(
        functools.partial(_kernel, block_pages=G, head_dim=d,
                          sm_scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n,),
            in_specs=[row] + pages + pages,
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((H, rep, 1), jnp.float32),
                            pltpu.VMEM((H, rep, 1), jnp.float32),
                            pltpu.VMEM((H, rep, lanes), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, rep, lanes), q.dtype),
        # in order on one core: the softmax state is carried over a slot's
        # blocks, and a partial block relies on what the step before fetched
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mx_paged_decode",
    )(lengths, slot, block, page,
      jnp.tile(q, (1, 1, per_row)).reshape(S, H, rep, lanes),
      *([k_pool] * G), *([v_pool] * G))
    out = out.reshape(S, Hq, per_row, d).sum(axis=2)
    # a slot with nothing alive has no grid step: its row was never written
    return jnp.where((lengths > 0)[:, None, None], out,
                     jnp.zeros((), q.dtype))


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

def paged_decode_attention(q, k_pool, v_pool, table, lengths, *,
                           k_scale=None, v_scale=None):
    """Attention of one query row per slot over the slot's live pages (see
    the module docstring). ``k_scale`` / ``v_scale``: the ``(n_pages, H)``
    scale planes of int8 pools."""
    if takes_kernel(k_pool.dtype):
        _dispatch.note("paged_decode_attention", "pallas")
        return _pallas_paged_decode(q, k_pool, v_pool, table, lengths,
                                    _dispatch.interpret_default())
    _dispatch.note("paged_decode_attention", "xla")
    return _xla_paged_decode(q, k_pool, v_pool, table, lengths,
                             k_scale, v_scale)


# ---------------------------------------------------------------------------
# latent pages (MLA): one shared row a token, queries absorbed
# ---------------------------------------------------------------------------

_MLA_BLOCK_PAGES = 32   # pages per grid step, at most (512 rows of 16)
# VMEM blocks the latent kernel fetches into, a block's copies started one
# fewer steps before it is read: from the first copy's start to the last
# one's end a block of 32 pages takes 1.3 us on a v5e, a grid step's products
# as long, so one step ahead the wait still shows (PERF.md, PR 34)
_MLA_BUFFERS = 3


def latent_store_width(width):
    """Lanes a latent row of `width` values is stored in: whole tiles."""
    return -(-width // LANES) * LANES


def _xla_mla_decode(q, pool, table, lengths, rank, sm_scale):
    S, P = table.shape
    view = jnp.take(pool, table, axis=0)                   # (S, P, pt, W)
    view = view.reshape(S, P * view.shape[2], view.shape[3])
    s = jnp.einsum("shw,srw->shr", q.astype(pool.dtype), view,
                   preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.arange(view.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(pool.dtype)
    o = jnp.einsum("shr,src->shc", p, view[..., :rank],
                   preferred_element_type=jnp.float32)
    return jnp.where((lengths > 0)[:, None, None], o, 0.0).astype(q.dtype)


def _mla_work_list(lengths, page_tokens, block_pages, n_blocks):
    """The latent kernel's grid, made from the lengths (a few scalar-sized
    XLA ops, the same for every layer of a step): one grid step per LIVE
    block of `block_pages` pages, slot after slot — ``n`` of them (at least
    one), then ``slot[i]``, ``block[i]`` and ``pages[i]``, the slot's live
    pages from the block's first on (more than `block_pages` where the slot
    goes on). The kernel reads a block's pages from the table itself."""
    S, G, NB = lengths.shape[0], block_pages, n_blocks
    n_pages = -(-lengths // page_tokens)                          # (S,)
    live_block = (jnp.arange(NB)[None, :] * G < n_pages[:, None]).reshape(-1)
    n = jnp.sum(live_block, dtype=jnp.int32)
    flat, = jnp.nonzero(live_block, size=S * NB, fill_value=0)
    flat = flat.astype(jnp.int32)
    slot, block = flat // NB, flat % NB
    return jnp.maximum(n, 1), slot, block, n_pages[slot] - block * G


def _mla_kernel(len_ref, slot_ref, block_ref, pages_ref, table_ref, q_ref,
                pool_ref, o_ref, *scratch, block_pages, table_pages,
                rank_lanes, sm_scale):
    G, P = block_pages, table_pages
    bufs = scratch[:_MLA_BUFFERS]
    sems, m_scr, l_scr, acc_scr = scratch[_MLA_BUFFERS:]
    ahead = _MLA_BUFFERS - 1    # a block's copies start this many steps early
    pt = bufs[0].shape[0] // G
    i = pl.program_id(0)
    n = pl.num_programs(0)
    j = block_ref[i]
    length = len_ref[slot_ref[i]]

    def start(step, buf, sem):
        """One copy a live page of `step`'s block: none for a page past the
        slot's length, none for a step past the last."""
        at = jnp.minimum(step, n - 1)
        pages = jnp.where(step < n, pages_ref[at], 0)
        first = slot_ref[at] * P + block_ref[at] * G    # in the flat table
        for g in range(G):
            @pl.when(g < pages)
            def _(g=g):
                pltpu.make_async_copy(pool_ref.at[table_ref[first + g]],
                                      buf.at[pl.ds(g * pt, pt)], sem).start()

    def wait(buf, sem):
        pages = pages_ref[i]

        @pl.when(pages >= G)        # the whole block: one wait for its bytes
        def _():
            pltpu.make_async_copy(buf, buf, sem).wait()

        page = buf.at[pl.ds(0, pt)]     # a page's bytes, `pages` times

        def one(_, carry):
            pltpu.make_async_copy(page, page, sem).wait()
            return carry

        jax.lax.fori_loop(0, jnp.where(pages < G, pages, 0), one, None)

    # Rows that are never fetched (past a slot's length) must hold numbers:
    # their weight is exp(-1e30) = 0, and 0 x NaN would poison the sum. So
    # every block starts as zeros and only real pages are ever written
    @pl.when(i == 0)
    def _():
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        for step in range(ahead):
            start(step, bufs[step], sems.at[step])

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(buf, sem, free, free_sem):
        wait(buf, sem)
        # the block as one (G pt, W) array, read where it is used: held as
        # a value it would be spilled between the two products
        s = jax.lax.dot_general(q_ref[...], buf[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        # a later step's pages into the block the step before this one
        # read, while this one is worked on: after the first product in
        # program order, so that the copies' address arithmetic lies under
        # the products
        start(i + ahead, free, free_sem)
        at = j * (G * pt) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(at < length, s, NEG_INF)                # (H, G pt)
        m = m_scr[...]                                        # (H, 1)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)              # a masked row: exp(-1e30) = 0
        m_scr[...] = m_new
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(buf.dtype), buf[:, :rank_lanes],
            preferred_element_type=jnp.float32)

    # blocks the compiler can tell apart, the body once for each: with one
    # array indexed by the step it orders a block's load after every start
    for r in range(_MLA_BUFFERS):
        @pl.when(i % _MLA_BUFFERS == r)
        def _(r=r):
            free = (r + ahead) % _MLA_BUFFERS
            step(bufs[r], sems.at[r], bufs[free], sems.at[free])

    # the slot's row of the output stays in VMEM until the slot changes:
    # its last block writes what goes back
    @pl.when((j + 1) * (G * pt) >= length)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "sm_scale", "interpret",
                                             "block_pages"))
def _pallas_mla_decode(q, pool, table, lengths, rank, sm_scale, interpret,
                       block_pages=_MLA_BLOCK_PAGES):
    S, H, W = q.shape
    n_pages, pt, Wp = pool.shape
    P = table.shape[1]
    if Wp != W:
        raise ValueError(f"pool {pool.shape} does not match q {q.shape}")
    rank_lanes = latent_store_width(rank)
    G = max(g for g in range(1, block_pages + 1) if P % g == 0)
    # no address can leave the pool whatever the table holds: the kernel's
    # copies carry no range checks of their own (`disable_bounds_checks`)
    table = jnp.clip(table.astype(jnp.int32), 0, n_pages - 1)
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, P * pt)
    n, slot, block, pages = _mla_work_list(lengths, pt, G, P // G)
    row = lambda i, lens, slot, *_: (slot[i], 0, 0)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_mla_kernel, block_pages=G, table_pages=P,
                          rank_lanes=rank_lanes, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n,),
            in_specs=[pl.BlockSpec((None, H, W), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, rank_lanes), row),
            scratch_shapes=[
                *[pltpu.VMEM((G * pt, W), pool.dtype)] * _MLA_BUFFERS,
                pltpu.SemaphoreType.DMA((_MLA_BUFFERS,)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, rank_lanes), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, rank_lanes), q.dtype),
        # in order on one core: the softmax state is carried over a slot's
        # blocks, and a step starts the copies a later one waits for
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        interpret=interpret,
        name="mx_mla_decode",
    )(lengths, slot, block, pages, table.reshape(-1), q.astype(pool.dtype),
      pool)
    # a slot with nothing alive has no grid step: its row was never written
    return jnp.where((lengths > 0)[:, None, None], out[..., :rank],
                     jnp.zeros((), q.dtype))


def mla_decode_attention(q, pool, table, lengths, *, rank, sm_scale,
                         impl=None):
    """Attention of one absorbed query a head a slot, ``q`` ``(S, H, W)``
    (``[q~ (rank) ; q_rope ; zeros]``), over the slot's live latent pages of
    ``pool`` ``(n_pages, page_tokens, W)``: the weighted sum of the rows'
    first `rank` values, ``(S, H, rank)`` in `q`'s dtype; zeros for a slot of
    length 0. `impl`: ``"pallas"`` / ``"xla"`` (tests); None chooses from
    what the process observes."""
    if impl is None:
        impl = "pallas" if _dispatch.use_pallas() else "xla"
        _dispatch.note("mla_decode_attention", impl)
    if impl == "pallas":
        return _pallas_mla_decode(q, pool, table, lengths, rank,
                                  float(sm_scale),
                                  _dispatch.interpret_default())
    return _xla_mla_decode(q, pool, table, lengths, rank, sm_scale)
