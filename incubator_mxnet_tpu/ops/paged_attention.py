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

- **pallas** (`mx_paged_decode`; one TPU device, float pools). The grid has
  one step per LIVE block of pages, slot after slot (`_block_list`: made from
  the lengths, scalar-prefetched; `_block_pages`: 32 pages a block at most
  and 1 MB of K, read from the page's bytes and the table's width). Each
  pool is ONE operand left in HBM and the table is scalar-prefetched flat: a
  step starts one copy a live page of a LATER step's block into one of
  `_BUFFERS` ``(H, G rows, lanes)`` VMEM blocks of K and of V, then waits for
  its own, started that many steps less one earlier. No copy for a page past
  a slot's length, so none for the trash page a dead entry names nor for a
  free slot's row; the copies carry no range checks
  (`disable_bounds_checks`): the table is clipped to the pool before the
  call. Rows never fetched hold zeros or an earlier page (every block is
  zeroed at the call's first step) and the mask gives them no weight. The
  body is written once a block, for the compiler orders loads after every
  copy into the same array. Which unit multiplies is read from the shape
  (`_on_mxu`; counted as ``...{op="paged_decode_products",impl="mxu"|
  "vpu"}``), never from a flag or a model's name:

  - a bfloat16 pool whose head fills whole 128-lane tiles: **two MXU
    products over the block**, per stored head ``(rep, d) x (G pt, d)^T``
    for the scores and ``(rep, G pt) x (G pt, d)`` for the sum, from the
    bfloat16 operands as they are stored into float32 (products of
    bfloat16 pairs summed in float32: what the VPU computed before), the
    scores scaled by ``1 / sqrt(d)`` AFTER the product, in float32; mask,
    max, `exp` and sum over ``(H, rep, G pt)`` with the rows in the lanes.
    The weights enter the second product ROUNDED TO THE POOL'S DTYPE
    (``p.astype(v.dtype)``), as the XLA expression below and
    `mx_mla_decode` hand them over: the one rounding the VPU's body does
    not make, inside bfloat16, the precision such a pool states. One query
    row a head (``rep == 1``) is the same code: the array holds a head's
    128 x 128 tile of K, then of V, and one row streams through it;
  - narrower heads packed side by side in a row, and float32 pools: **a VPU
    pass a live page** over the same fetched block, exact float32 products
    (no MXU pass narrows them), online softmax a page at a time.
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
``mx_mla_decode`` has the grid and the fetch of `mx_paged_decode` (one work
list, `_block_list`; one way to start and to wait for a block's copies,
`_start_pages` / `_wait_pages`): a step a live block of 32 pages, the pool
one operand left in HBM, one copy a live page into one of `_BUFFERS`
``(G pt, W)`` VMEM blocks two steps before it is read. (An operand a page,
each pipelined through its own `BlockSpec`, cost a grid step more scalar
bookkeeping than the products took: `tools/kernel_schedule.py`.) The copies
start after the first product in program order and their address arithmetic
lies under the products. A step's products are MXU products, bfloat16 into
float32, each with the SLOT'S side held in the array and the block's rows
streamed through it: the scores turned, ``(rows, W) x (W, H)``, against q,
and the sum turned, ``(rank, rows) x (rows, H)``, against the weights, V's
rows turned on their way. So the array is loaded with the slot's side,
never with the block: 160 weight pushes a step of 512 rows where holding
the block's 36 tiles took 288 (`tools/kernel_schedule.py`). Between them
the online softmax in float32, rows in the sublanes and heads in the lanes.
The block is worked in two halves, each its own softmax update, and both
halves' scores are written before the first half's sum (counted as
``mx_kernel_dispatch_total{op="mla_decode_products",
impl="queries_held_overlapped"}``): the MXU scores the second half while the
VPU does the first's softmax, and sums the first while the VPU does the
second's, where one softmax over the whole block left the array idle an
eighth of the step. A slot's last block divides and stores the output row
turned back, ``(H, rank)``. At 128 heads that is 2 x 128 x (576 + 512)
operations a row of 1,152 useful bytes, 242 op/B: on the v5e's ridge. The
XLA expression (the CPU, a mesh) gathers every slot's view.
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
# the kernels' grid and fetch (both kernels of this file)
# ---------------------------------------------------------------------------

_BLOCK_PAGES = 32       # pages per grid step, at most (512 rows of 16) ...
_BLOCK_BYTES = 1 << 20  # ... and bytes of them (of K; V's are as many more)
# VMEM blocks a kernel fetches into, a block's copies started one fewer
# steps before it is read: from the first copy's start to the last one's end
# a block of 32 latent pages takes 1.3 us on a v5e, a grid step's products as
# long, so one step ahead the wait still shows (PERF.md, PR 34)
_BUFFERS = 3


def _block_pages(table_pages, page_bytes, at_most=_BLOCK_PAGES):
    """Pages a grid step: read from the page's bytes and the table's width,
    the most that divide the table, fill no more than `_BLOCK_BYTES` and
    number no more than `at_most`. Small pages go 32 to a block (a step's
    fixed ~0.35 us would outweigh the work of fewer), 128 KB pages 8."""
    cap = max(1, min(at_most, _BLOCK_BYTES // page_bytes))
    return max(g for g in range(1, cap + 1) if table_pages % g == 0)


def _block_list(lengths, page_tokens, block_pages, n_blocks):
    """A kernel's grid, made from the lengths (a few scalar-sized XLA ops,
    the same for every layer of a step): one grid step per LIVE block of
    `block_pages` pages, slot after slot — ``n`` of them (at least one),
    then ``slot[i]``, ``block[i]`` and ``pages[i]``, the slot's live pages
    from the block's first on (more than `block_pages` where the slot goes
    on). The kernel reads a block's pages from the table itself."""
    S, G, NB = lengths.shape[0], block_pages, n_blocks
    n_pages = -(-lengths // page_tokens)                          # (S,)
    live_block = (jnp.arange(NB)[None, :] * G < n_pages[:, None]).reshape(-1)
    n = jnp.sum(live_block, dtype=jnp.int32)
    flat, = jnp.nonzero(live_block, size=S * NB, fill_value=0)
    flat = flat.astype(jnp.int32)
    slot, block = flat // NB, flat % NB
    return jnp.maximum(n, 1), slot, block, n_pages[slot] - block * G


def _prefetched(table, lengths, n_pages, page_tokens, block_pages):
    """What both kernels are scalar-prefetched with: the lengths held to the
    view, the grid (`_block_list`) and the table, flat. No address can leave
    the pool whatever the table holds: it is clipped to the pool here,
    because the kernels' copies carry no range checks of their own
    (`disable_bounds_checks`)."""
    P = table.shape[1]
    table = jnp.clip(table.astype(jnp.int32), 0, n_pages - 1)
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, P * page_tokens)
    n, slot, block, pages = _block_list(lengths, page_tokens, block_pages,
                                        P // block_pages)
    return n, (lengths, slot, block, pages, table.reshape(-1))


def _start_pages(work, step, copies):
    """Start the copies of grid step `step`'s block, a live page at a time:
    none for a page past the slot's length (so none for the trash page a
    dead table entry names), none for a step past the last. `work` is the
    scalar-prefetched ``(slot_ref, block_ref, pages_ref, table_ref)``, the
    table's and a block's width in pages and the grid's steps;
    ``copies(page, g)`` gives the copies that bring pool page `page` to the
    block's `g`-th place."""
    slot_ref, block_ref, pages_ref, table_ref, P, G, n = work
    at = jnp.minimum(step, n - 1)
    pages = jnp.where(step < n, pages_ref[at], 0)
    first = slot_ref[at] * P + block_ref[at] * G    # in the flat table
    for g in range(G):
        @pl.when(g < pages)
        def _(g=g):
            for copy in copies(table_ref[first + g], g):
                copy.start()


def _wait_pages(pages, block_pages, whole, page):
    """Wait for the copies `_start_pages` started for a block with `pages`
    live pages: `whole` are copies the size of the whole block (one wait
    for all its bytes), `page` copies the size of one page of it."""
    @pl.when(pages >= block_pages)
    def _():
        for copy in whole:
            copy.wait()

    def one(_, carry):
        for copy in page:
            copy.wait()
        return carry

    jax.lax.fori_loop(0, jnp.where(pages < block_pages, pages, 0), one, None)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _on_mxu(q, k_pool):
    """True where the kernel's two products go to the MXU: a head fills
    whole 128-lane tiles and the pool is bfloat16, so a block of K (then of
    V) is the array's operand as it is stored. Narrower heads, packed side
    by side in a row, and float32 pools keep the VPU's exact products."""
    d = q.shape[-1]
    return (k_pool.shape[-1] == d and d % LANES == 0
            and k_pool.dtype == jnp.bfloat16)


def _kernel(len_ref, slot_ref, block_ref, pages_ref, table_ref, q_ref,
            k_pool, v_pool, o_ref, *scratch, block_pages, table_pages,
            head_dim, sm_scale, on_mxu):
    G, d = block_pages, head_dim
    k_bufs, v_bufs = scratch[:_BUFFERS], scratch[_BUFFERS:2 * _BUFFERS]
    sems, m_scr, l_scr, acc_scr = scratch[2 * _BUFFERS:]
    ahead = _BUFFERS - 1    # a block's copies start this many steps early
    H, block_rows, lanes = k_bufs[0].shape
    rows = block_rows // G              # of one page
    rep = q_ref.shape[1]                # query heads a stored head
    per_row = lanes // d                # tokens side by side in a row
    i = pl.program_id(0)
    j = block_ref[i]                    # which block of its slot's pages
    length = len_ref[slot_ref[i]]
    work = (slot_ref, block_ref, pages_ref, table_ref, table_pages, G,
            pl.num_programs(0))

    def start(step, b):
        def copies(page, g):
            at = (slice(None), pl.ds(g * rows, rows))
            return [pltpu.make_async_copy(pool.at[page], bufs[b].at[at],
                                          sems.at[b])
                    for pool, bufs in ((k_pool, k_bufs), (v_pool, v_bufs))]
        _start_pages(work, step, copies)

    def wait(b):
        whole, page = [], []
        for buf in (k_bufs[b], v_bufs[b]):
            first = buf.at[:, pl.ds(0, rows)]
            whole.append(pltpu.make_async_copy(buf, buf, sems.at[b]))
            page.append(pltpu.make_async_copy(first, first, sems.at[b]))
        _wait_pages(pages_ref[i], G, whole, page)

    # Rows that are never fetched (past a slot's length) must hold numbers:
    # their weight is exp(-1e30) = 0, and 0 x NaN would poison the sum. So
    # every block starts as zeros and only real pages are ever written
    @pl.when(i == 0)
    def _():
        for buf in k_bufs + v_bufs:
            buf[...] = jnp.zeros_like(buf)
        for step in range(ahead):
            start(step, step)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def products(kb, vb, fetch_next):
        """The block on the MXU: per stored head, its `rep` query rows
        against the block's rows, the rows in the lanes through mask, max,
        `exp` and sum, and the weights against V. bfloat16 pairs summed in
        float32; the scores are scaled after the product, in float32."""
        s = jax.lax.dot_general(q_ref[...], kb[...],
                                (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * sm_scale
        fetch_next()        # its address arithmetic lies under the products
        at = j * block_rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(at < length, s, NEG_INF)          # (H, rep, rows)
        m = m_scr[...]                                  # (H, rep, 1)
        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)              # a masked row: exp(-1e30) = 0
        m_scr[...] = m_new
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(vb.dtype), vb[...], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    def passes(kb, vb, fetch_next):
        """The block on the VPU, a live page at a time: exact float32
        products, `per_row` tokens side by side in a row of the page."""
        fetch_next()
        # the query, once per token of a row: (H, rep, lanes)
        q = q_ref[...].astype(jnp.float32) * sm_scale
        row = jax.lax.broadcasted_iota(jnp.int32, (H, rows, 1), 1)
        if per_row > 1:                 # the lanes of a row's c-th token
            lane = jax.lax.broadcasted_iota(jnp.int32, (H, rows, lanes), 2)
            seg = [(lane >= c * d) & (lane < (c + 1) * d)
                   for c in range(per_row)]

        for g in range(G):
            first = (j * G + g) * rows * per_row    # the page's first position

            # a page wholly past the length was not fetched (its rows hold
            # zeros or an earlier page): it takes no part
            @pl.when(g < pages_ref[i])
            def _(g=g, first=first):
                page = (slice(None), slice(g * rows, (g + 1) * rows))
                k = kb[page].astype(jnp.float32)        # (H, rows, lanes)
                v = vb[page].astype(jnp.float32)
                for r in range(rep):    # the stored head's r-th query head
                    at = (Ellipsis,) if rep == 1 else (slice(None),
                                                       slice(r, r + 1))
                    kq = k * (q if rep == 1 else q[at])
                    sc = []             # per token of a row: (H, rows, 1)
                    for c in range(per_row):
                        part = kq if per_row == 1 else jnp.where(seg[c], kq,
                                                                 0.0)
                        s_c = jnp.sum(part, axis=-1, keepdims=True)
                        alive = first + row * per_row + c < length
                        sc.append(jnp.where(alive, s_c, NEG_INF))
                    m = m_scr[at]
                    m_new = m
                    for s_c in sc:
                        m_new = jnp.maximum(
                            m_new, jnp.max(s_c, axis=1, keepdims=True))
                    alpha = jnp.exp(m - m_new)            # (H, 1, 1)
                    p = [jnp.exp(s_c - m_new) for s_c in sc]
                    w = p[0]            # each token's weight on its lanes
                    for c in range(1, per_row):
                        w = jnp.where(seg[c], p[c], w)
                    m_scr[at] = m_new
                    l_scr[at] = alpha * l_scr[at] + sum(
                        jnp.sum(p_c, axis=1, keepdims=True) for p_c in p)
                    acc_scr[at] = alpha * acc_scr[at] + jnp.sum(
                        w * v, axis=1, keepdims=True)     # (H, 1, lanes)

    # blocks the compiler can tell apart, the body once for each: with one
    # array indexed by the step it orders a block's load after every start
    for b in range(_BUFFERS):
        @pl.when(i % _BUFFERS == b)
        def _(b=b):
            wait(b)
            # a later step's pages into the block the step before this one
            # read, while this one is worked on
            (products if on_mxu else passes)(
                k_bufs[b], v_bufs[b],
                lambda: start(i + ahead, (b + ahead) % _BUFFERS))

    # the slot's row of the output stays in VMEM until the slot changes:
    # its last block writes what goes back. With narrow heads still one
    # partial sum per token of a row; the caller adds them
    @pl.when((j + 1) * (block_rows * per_row) >= length)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
                      ).astype(o_ref.dtype)


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
    on_mxu = _on_mxu(q, k_pool)
    G = _block_pages(P, H * rows * lanes * k_pool.dtype.itemsize)
    n, scalars = _prefetched(table, lengths, n_pages, pt, G)
    rows_q = jnp.tile(q, (1, 1, per_row)).reshape(S, H, rep, lanes)
    row = pl.BlockSpec((None, H, rep, lanes),
                       lambda i, lens, slot, *_: (slot[i], 0, 0, 0))
    block_shape = (H, G * rows, lanes)
    out = pl.pallas_call(
        functools.partial(_kernel, block_pages=G, table_pages=P, head_dim=d,
                          sm_scale=1.0 / math.sqrt(d), on_mxu=on_mxu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                *[pltpu.VMEM(block_shape, k_pool.dtype)] * (2 * _BUFFERS),
                pltpu.SemaphoreType.DMA((_BUFFERS,)),
                pltpu.VMEM((H, rep, 1), jnp.float32),
                pltpu.VMEM((H, rep, 1), jnp.float32),
                pltpu.VMEM((H, rep, lanes), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, rep, lanes), q.dtype),
        # in order on one core: the softmax state is carried over a slot's
        # blocks, and a step starts the copies a later one waits for
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        interpret=interpret,
        name="mx_paged_decode",
    )(*scalars, rows_q.astype(k_pool.dtype) if on_mxu else rows_q, k_pool,
      v_pool)
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
        _dispatch.note("paged_decode_products",
                       "mxu" if _on_mxu(q, k_pool) else "vpu")
        return _pallas_paged_decode(q, k_pool, v_pool, table, lengths,
                                    _dispatch.interpret_default())
    _dispatch.note("paged_decode_attention", "xla")
    return _xla_paged_decode(q, k_pool, v_pool, table, lengths,
                             k_scale, v_scale)


# ---------------------------------------------------------------------------
# latent pages (MLA): one shared row a token, queries absorbed
# ---------------------------------------------------------------------------

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


def _mla_kernel(len_ref, slot_ref, block_ref, pages_ref, table_ref, q_ref,
                pool_ref, o_ref, *scratch, block_pages, table_pages,
                rank_lanes, sm_scale):
    G = block_pages
    bufs = scratch[:_BUFFERS]
    sems, m_scr, l_scr, acc_scr = scratch[_BUFFERS:]
    ahead = _BUFFERS - 1    # a block's copies start this many steps early
    pt = bufs[0].shape[0] // G
    i = pl.program_id(0)
    j = block_ref[i]
    length = len_ref[slot_ref[i]]
    work = (slot_ref, block_ref, pages_ref, table_ref, table_pages, G,
            pl.num_programs(0))

    def start(step, buf, sem):
        _start_pages(work, step, lambda page, g: [pltpu.make_async_copy(
            pool_ref.at[page], buf.at[pl.ds(g * pt, pt)], sem)])

    def wait(buf, sem):
        page = buf.at[pl.ds(0, pt)]
        _wait_pages(pages_ref[i], G, [pltpu.make_async_copy(buf, buf, sem)],
                    [pltpu.make_async_copy(page, page, sem)])

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

    # The block's rows in two halves of whole pages where its pages divide
    # evenly, each its own online-softmax update; a half's sum in two
    # products over halves of the rank where it divides into whole tiles.
    # The MXU takes its work in program order, so both halves' scores come
    # before the first half's sum: the array scores the second half while
    # the VPU and EUP do the first half's softmax, and sums the first half
    # while they do the second's. The compiler gives each held tile's
    # stream of rows to one of the four MXUs: over the whole rank a half's
    # sum is two streams of 512 rows, over its halves four of 256, so the
    # last half's sum takes all four MXUs after its softmax, not two
    rows = G // 2 * pt if G % 2 == 0 else G * pt
    cols = rank_lanes // 2 if rank_lanes % (2 * LANES) == 0 else rank_lanes
    firsts = range(0, G * pt, rows)
    parts = range(0, rank_lanes, cols)

    def step(buf, sem, free, free_sem):
        wait(buf, sem)
        # Both products keep the slot's side in the MXU and stream the
        # block's rows through it: the scores turned, (rows, H), with q
        # held; the sum turned, (cols, H), with the weights held. The rows
        # are read where they are used: held as a value they would be
        # spilled between the two products
        scores = []
        for first in firsts:
            scores.append(jax.lax.dot_general(
                buf[pl.ds(first, rows), :], q_ref[...],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale)
            if first == 0:
                # a later step's pages into the block the step before this
                # one read, while this one is worked on: after the first
                # product in program order, so that the copies' address
                # arithmetic lies under the products
                start(i + ahead, free, free_sem)
        m, l = m_scr[...], l_scr[...]                         # (1, H)
        acc = [acc_scr[pl.ds(c, cols), :] for c in parts]     # (cols, H)
        for first, s in zip(firsts, scores):
            at = (j * (G * pt) + first
                  + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            s = jnp.where(at < length, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)          # a masked row: exp(-1e30) = 0
            l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
            p = p.astype(buf.dtype)
            acc = [alpha * a + jax.lax.dot_general(
                buf[pl.ds(first, rows), pl.ds(c, cols)], p,
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                for a, c in zip(acc, parts)]
            m = m_new
        m_scr[...], l_scr[...] = m, l
        for a, c in zip(acc, parts):
            acc_scr[pl.ds(c, cols), :] = a

    # blocks the compiler can tell apart, the body once for each: with one
    # array indexed by the step it orders a block's load after every start
    for r in range(_BUFFERS):
        @pl.when(i % _BUFFERS == r)
        def _(r=r):
            free = (r + ahead) % _BUFFERS
            step(bufs[r], sems.at[r], bufs[free], sems.at[free])

    # the slot's row of the output stays in VMEM until the slot changes:
    # its last block writes what goes back, turned back to (H, rank)
    @pl.when((j + 1) * (G * pt) >= length)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
                      ).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "sm_scale", "interpret",
                                             "block_pages"))
def _pallas_mla_decode(q, pool, table, lengths, rank, sm_scale, interpret,
                       block_pages=_BLOCK_PAGES):
    S, H, W = q.shape
    n_pages, pt, Wp = pool.shape
    P = table.shape[1]
    if Wp != W:
        raise ValueError(f"pool {pool.shape} does not match q {q.shape}")
    rank_lanes = latent_store_width(rank)
    G = _block_pages(P, pt * W * pool.dtype.itemsize, block_pages)
    n, scalars = _prefetched(table, lengths, n_pages, pt, G)
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
                *[pltpu.VMEM((G * pt, W), pool.dtype)] * _BUFFERS,
                pltpu.SemaphoreType.DMA((_BUFFERS,)),
                pltpu.VMEM((1, H), jnp.float32),
                pltpu.VMEM((1, H), jnp.float32),
                pltpu.VMEM((rank_lanes, H), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, rank_lanes), q.dtype),
        # in order on one core: the softmax state is carried over a slot's
        # blocks, and a step starts the copies a later one waits for
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        interpret=interpret,
        name="mx_mla_decode",
    )(*scalars, q.astype(pool.dtype), pool)
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
        _dispatch.note("mla_decode_products", "queries_held_overlapped")
        return _pallas_mla_decode(q, pool, table, lengths, rank,
                                  float(sm_scale),
                                  _dispatch.interpret_default())
    return _xla_mla_decode(q, pool, table, lengths, rank, sm_scale)
