"""The KV page as it is stored, and the cache-access objects that alone know it.

**The pools** (`make_pools`) are ONE pytree, ``{"k": leaves, "v": leaves}``
with a tuple of one leaf a layer each: every serving program takes it as one
donated argument and returns it, whatever the page format, so a program has
one signature. A float leaf holds ``(n_pages, H, page_tokens, d)`` values, a
head's ``(page_tokens, d)`` plane packed to 128 lanes (`ops.paged_attention`,
"a page as it is stored"). An int8 leaf holds them as int8 and the tree gains
``"sk"`` / ``"sv"``: one float32 scale per (page, head) a layer, the symmetric
±127 convention of `contrib.quantization`. Every leaf is ``(n_pages, H, ...)``:
a layout that shards heads shards them all alike (`serve/sharded.py`).
A family whose geometry says ``"latent"`` in the place of the heads (MLA:
`models/pangu.py`) has ONE leaf a layer, ``{"c": leaves}`` of ``(n_pages,
page_tokens, W)`` rows shared by every head (`ops.paged_attention`, "latent
pages"); its cache objects are `serve/mla.py`'s. A family whose layers differ
in kind (`decoder.layer_kinds()`) has a page leaf for each layer that keeps
pages — `geometry[0]` of them, not one a layer — and may add leaves that are
not pages at all to the same pytree: `serve/ssm.py`'s recurrent state,
``"ssm"`` / ``"conv"``, indexed by slot, which `PageCache` carries through a
program beside the page leaves and never reads.

**A cache-access object** is what a serving program hands a decoder's block
(`GPTDecoder.layer`, `EvaByteDecoder.layer`) in place of a cache::

    cache.attend(li, q, k, v) -> o

writes the rows ``k, v`` of layer ``li`` into the pool and returns the
attention of ``q`` over what the cache holds for those queries, the rows just
written among them. `PageCache` owns the pool leaves of one traced program and
the three things that depend on a page's stored form — writing whole pages,
writing one row a slot, reading pages back as rows; its subclasses add who
attends what: `TokenCache` (one new row a slot: decode, and each step of a
draft), `ChunkCache` (one prefill chunk of one slot, pages mapped by
position), `RowsCache` (k + 1 rows a slot: verify). A family whose pages are
not mapped by position brings its own chunk cache (`serve/eva.py`).
"""
from __future__ import annotations

import math

__all__ = ["make_pools", "page_bytes", "PageCache", "TokenCache", "ChunkCache",
           "RowsCache"]


def _j():
    import jax

    return jax


def _leaf_shapes(n_pages, page_tokens, geometry, kv_dtype):
    """``{leaf kind: (shape, dtype)}`` of ONE layer's pool leaves."""
    import numpy as onp

    _, H, d, dtype = geometry
    if H == "latent":
        from ..ops.paged_attention import latent_store_width

        if kv_dtype == "int8":
            raise NotImplementedError(
                "latent pages are not stored as int8: a per-page scale over "
                "a row whose halves are a normed latent and a rotated key "
                "is not worked out")
        return {"c": ((n_pages, page_tokens, latent_store_width(d)),
                      onp.dtype(dtype))}
    if kv_dtype == "int8":
        page = ((n_pages, H, page_tokens, d), onp.dtype("int8"))
        scale = ((n_pages, H), onp.dtype("float32"))
        return {"k": page, "v": page, "sk": scale, "sv": scale}
    from ..ops.paged_attention import page_store_shape

    page = ((n_pages, H) + page_store_shape(page_tokens, d), onp.dtype(dtype))
    return {"k": page, "v": page}


def make_pools(n_pages, page_tokens, geometry, kv_dtype):
    """The pools pytree of a model whose K/V rows are `geometry` =
    ``(layers, heads, head size, float dtype)`` (or ``(layers, "latent", row
    width, float dtype)``), stored as `kv_dtype`
    (``"fp"`` | ``"int8"``), zeroed. Separate leaves a layer, not one
    stacked 5-D array: `serve/engine.py`'s docstring says why."""
    jnp = _j().numpy
    shapes = _leaf_shapes(n_pages, page_tokens, geometry, kv_dtype)
    return {n: tuple(jnp.zeros(shape, dt) for _ in range(geometry[0]))
            for n, (shape, dt) in shapes.items()}


def page_bytes(page_tokens, geometry, kv_dtype):
    """Bytes one page holds across all layers and leaf kinds."""
    shapes = _leaf_shapes(1, page_tokens, geometry, kv_dtype)
    return geometry[0] * sum(math.prod(shape) * dt.itemsize
                             for shape, dt in shapes.values())


class PageCache:
    """The pool leaves of one traced program (`pools`, as `make_pools` gives
    them), updated in place as the layers run, and a page's stored form."""

    def __init__(self, eng, pools):
        self.eng = eng
        self.leaves = {n: list(a) for n, a in pools.items()}
        self.int8 = "sk" in pools

    def pools(self):
        """The updated pytree, for the program to return (pinned to the
        layout the engine keeps its pools in)."""
        return self.eng._constrain_pools(
            {n: tuple(a) for n, a in self.leaves.items()})

    def write_pages(self, li, pages, k, v):
        """Whole pages: ``k, v`` ``(n, H, page_tokens, d)`` values go to
        pages `pages` of layer `li` (int8: with their scales)."""
        from ..contrib.quantization import quantize_symmetric
        from ..ops.paged_attention import pack_pages

        for n, t in (("k", k), ("v", v)):
            pool = self.leaves[n]
            if self.int8:
                tq, ts = quantize_symmetric(t, axes=(2, 3))
                scale = self.leaves["s" + n]
                pool[li] = pool[li].at[pages].set(tq)
                scale[li] = scale[li].at[pages].set(ts[:, :, 0, 0])
            else:
                pool[li] = pool[li].at[pages].set(
                    pack_pages(t.astype(pool[li].dtype)))

    def write_rows(self, li, wpage, woff, k, v):
        """One row a slot: ``k, v`` ``(S, H, d)`` go to row `woff` of page
        `wpage` of each slot; int8 re-quantizes just the written page under
        a grow-only scale."""
        jnp = _j().numpy
        from ..contrib.quantization import quantize_symmetric
        from ..ops.paged_attention import pack_pages, unpack_pages

        for n, t in (("k", k), ("v", v)):
            pool = self.leaves[n]
            if not self.int8:
                # whole pages out, the token's row set, whole pages back:
                # a page is one contiguous block of the leaf, so the
                # update runs in place. (A scatter of (H, d) rows makes
                # the TPU's compiler turn the whole leaf to a layout with
                # H beside d, and back.)
                page = unpack_pages(jnp.take(pool[li], wpage, axis=0),
                                    t.shape[-1])               # (S,H,pt,d)
                row = jnp.arange(page.shape[2])[None, None, :, None]
                page = jnp.where(
                    row == woff[:, None, None, None],
                    t.astype(pool[li].dtype)[:, :, None, :], page)
                pool[li] = pool[li].at[wpage].set(pack_pages(page))
                continue
            scale = self.leaves["s" + n]
            old = jnp.take(scale[li], wpage, axis=0)           # (S, H)
            amax = jnp.max(jnp.abs(t), axis=-1)                # (S, H)
            new = jnp.maximum(old, jnp.maximum(amax, 1e-8) / 127.0)
            page = jnp.take(pool[li], wpage, axis=0)           # (S,H,pt,d)
            page = jnp.clip(
                jnp.round(page.astype(jnp.float32)
                          * (old / new)[:, :, None, None]),
                -127, 127)
            tq, _ = quantize_symmetric(t, axes=(), scale=new[:, :, None])
            page = page.at[jnp.arange(t.shape[0]), :, woff].set(tq)
            pool[li] = pool[li].at[wpage].set(page.astype(jnp.int8))
            scale[li] = scale[li].at[wpage].set(new)

    def rows(self, li, idx):
        """Pages `idx` of layer `li` read back as real-valued rows: ``(K,
        V)``, each ``(..., H, n * page_tokens, d)`` for `idx` ``(..., n)``
        (float pools gather straight through, int8 ones are scaled)."""
        jnp = _j().numpy
        from ..ops.paged_attention import unpack_pages

        out = []
        for n in ("k", "v"):
            t = jnp.take(self.leaves[n][li], idx, axis=0)
            t = unpack_pages(
                t, t.shape[-2] * t.shape[-1] // self.eng.page_tokens)
            if self.int8:
                sc = jnp.take(self.leaves["s" + n][li], idx, axis=0)
                t = t.astype(jnp.float32) * sc[..., None, None]
            *lead, n_idx, H, pt, d = t.shape
            out.append(jnp.moveaxis(t, -3, -4).reshape(
                *lead, H, n_idx * pt, d))
        return out


class TokenCache(PageCache):
    """Cache access of one new row a slot (a decode step; each step of a
    draft): the slot's row goes to entry `col` of its row of the page table
    at offset `woff`, and its query attends the table row as far as `rows`
    (the slots object's page arithmetic, `SlotDecoder._row_of`). A slot that
    is not `active` (free, or still prefilling) writes to the trash page and
    attends nothing; one that is not `writable` (a drafted row past its
    budget) writes to the trash page."""

    def __init__(self, eng, pools, table, col, woff, rows, active,
                 writable=None):
        jnp = _j().numpy
        super().__init__(eng, pools)
        wpage = table[jnp.arange(table.shape[0]), col]
        self.wpage = jnp.where(active if writable is None else writable,
                               wpage, 0)
        self.table, self.woff = table, woff
        self.lengths = jnp.where(active, rows, 0)

    def attend(self, li, q, k, v):
        """``q, k, v``: a row a slot, ``(S, H, d)`` (or the GPT block's
        ``(S, H, 1, d)``); returns ``(S, H, d)``."""
        from ..ops.paged_attention import paged_decode_attention

        if q.ndim == 4:
            q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
        # the new token's K/V is in the pool before attention reads it
        self.write_rows(li, self.wpage, self.woff, k, v)
        sk, sv = (self.leaves["sk"][li], self.leaves["sv"][li]) \
            if self.int8 else (None, None)
        with self.eng._mesh_scope():
            return paged_decode_attention(
                q, self.leaves["k"][li], self.leaves["v"][li], self.table,
                self.lengths, k_scale=sk, v_scale=sv)


class ChunkCache(PageCache):
    """Cache access of one prefill chunk of one slot whose pages are mapped
    by position: the chunk's rows go into `chunk_pages`, and its queries
    (positions ``t_start ..``) attend the slot's whole view `pages_row`
    under a causal-with-offset mask."""

    def __init__(self, eng, pools, pages_row, chunk_pages, t_start):
        jnp = _j().numpy
        super().__init__(eng, pools)
        self.pages_row, self.chunk_pages = pages_row, chunk_pages
        self.t_start = t_start
        # causal-with-offset validity: key position j is visible to
        # chunk row i iff j <= t_start + i — this covers BOTH the
        # prefix pages (j < t_start) and in-chunk causality, and
        # masks stale/trash/padding pages in one stroke
        pt = eng.page_tokens
        qpos = t_start + jnp.arange(chunk_pages.shape[0] * pt)
        self.mask = jnp.arange(pages_row.shape[0] * pt)[None, :] \
            <= qpos[:, None]

    def attend(self, li, q, k, v):
        """``q, k, v`` ``(1, H, C, d)``; returns ``(1, C, H, d)``."""
        jax = _j()
        jnp = jax.numpy
        pt = self.eng.page_tokens
        _, H, C, d = q.shape

        def to_pages(t):           # (1, H, C, d) -> (C // pt, H, pt, d)
            return jnp.transpose(
                t[0].transpose(1, 0, 2).reshape(C // pt, pt, H, d),
                (0, 2, 1, 3))

        self.write_pages(li, self.chunk_pages, to_pages(k), to_pages(v))
        vk, vv = (r[None] for r in self.rows(li, self.pages_row))
        if self.int8:
            # the chunk attends to its OWN K/V exactly (pre-
            # quantization) — only the prefix pays quantization
            at = (0, 0, self.t_start, 0)
            vk = jax.lax.dynamic_update_slice(vk, k.astype(vk.dtype), at)
            vv = jax.lax.dynamic_update_slice(vv, v.astype(vv.dtype), at)
        # mirror ops/flash_attention._xla_attention exactly (the impl the
        # unpaged `GPTDecoder.generate` prefill resolves to at serving
        # sizes) so paged output stays bit-identical
        s = jnp.einsum("bhqd,bhkd->bhqk", q, vk) * (1.0 / math.sqrt(d))
        neg = jnp.asarray(jnp.finfo(s.dtype).min / 2, s.dtype)
        s = jnp.where(self.mask[None, None], s, neg)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, vv)
        return jnp.transpose(o, (0, 2, 1, 3))


class RowsCache(PageCache):
    """Cache access of verify's ``k + 1`` rows a slot at positions `p_abs`
    ``(S, K1)``: row ``i`` goes to row ``woff[:, i]`` of page ``wpage[:,
    i]`` and attends the slot's view up to its own position."""

    def __init__(self, eng, pools, table, wpage, woff, p_abs):
        jnp = _j().numpy
        super().__init__(eng, pools)
        self.table, self.wpage, self.woff = table, wpage, woff
        # (S, K1, PT) causal-per-row validity
        self.mask = jnp.arange(table.shape[1] * eng.page_tokens)[
            None, None, :] <= p_abs[:, :, None]

    def attend(self, li, q, k, v):
        """``q, k, v`` ``(S, H, K1, d)``; returns ``(S, K1, H, d)``."""
        jax = _j()
        jnp = jax.numpy
        kt = jnp.transpose(k, (0, 2, 1, 3))                    # (S,K1,H,d)
        vt = jnp.transpose(v, (0, 2, 1, 3))
        # column-at-a-time writes reuse the decode row write exactly
        # (int8 grow-only rescale order preserved)
        for i in range(q.shape[2]):
            self.write_rows(li, self.wpage[:, i], self.woff[:, i],
                            kt[:, i], vt[:, i])
        vk, vv = self.rows(li, self.table)                     # (S,H,PT,d)
        s = jnp.einsum("shqd,shkd->shqk", q, vk,
                       preferred_element_type=jnp.float32)
        s = s / math.sqrt(q.shape[-1])
        s = jnp.where(self.mask[:, None, :, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(vv.dtype)
        o = jnp.einsum("shqk,shkd->shqd", p, vv)
        return jnp.transpose(o, (0, 2, 1, 3))
