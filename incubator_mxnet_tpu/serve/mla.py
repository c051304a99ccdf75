"""`mx.serve` for the latent-attention (MLA) family: one page leaf of latent
rows shared by every head, attended two ways.

A token leaves ONE row a layer in the cache, ``[c_kv ; k_rope]``
(`models/pangu.py`): 576 values at the published sizes, 1,152 useful bytes in
bfloat16 against the 81,920 of 128 heads' keys and values. The pool leaf of a
layer is ``(n_pages, page_tokens, W)`` with ``W`` the row rounded up to whole
128-lane tiles (640: a row takes 1,280 B, as the chip's tiling would pad a
576-wide row anyway; `ops.paged_attention`, "latent pages"). Pages are mapped
by position, all of a request's at admission, exactly as the GPT block's:
the page arithmetic (`pages_needed`, `pages_at`, `_table_width`, `_row_of`)
is `SlotDecoder`'s own, and so are the programs. What is brought here is the
two cache-access objects, one family attending its rows in two forms:

- **decode, absorbed** (`_TokenCache`): the slot's new row is written, the
  queries are folded through ``w_uk`` into the latent space, the kernel
  ``mx_mla_decode`` (`ops.paged_attention.mla_decode_attention`) scores them
  against the live latent pages and sums ``c_kv`` under the softmax, and the
  sum goes through ``w_uv``. No per-head key or value is ever made.
- **a prefill chunk, up-projected** (`_ChunkCache`): the chunk's rows are
  written, then the slot's rows up to the chunk's end are read back a block
  of pages at a time, expanded to per-head keys and values, and attended
  under an online softmax — for 512 queries that is half the operations of
  the absorbed form, and the loop runs as far as the chunk's end, not over
  the slot's whole view.

The expert layers count, inside the program, the (token, expert) pairs that
fell on experts held here and the distinct held experts they hit
(`ops.moe.held_experts`); the chunk and decode programs append those
``(expert layers, 2)`` int32, and the number of real rows they routed, to the
tokens they return (`_step_out`), so they
reach the host in the tokens' own fetch, and `fetch_tokens` / `fetch_first`
take them off again into the step record (``moe_pairs_held``,
``moe_experts_hit``, ``moe_pairs_routed``) and the series
``mx_serve_moe_pairs_total{kind="held"|"routed"}`` and
``mx_serve_moe_experts_hit_total``. (A chunk that is not its prompt's last is
never fetched, and is not counted.)

Prefix reuse works as for the GPT block (a latent page holds exact rows of
known positions, keyed by the token prefix). Not served for this family, each
refused with `NotImplementedError`: speculative decoding (``spec_k > 0``,
``draft``; the release's multi-token-prediction module is not held — ROADMAP
R7), int8 pages, prefill-only handoff and adoption (`page_handoff`), and the
sharded engine (`serve/sharded.py`).
"""
from __future__ import annotations

import math
import os
import weakref

import numpy as onp

from ..models.pangu import PanguDecoder
from ..telemetry import registry, tracing
from .engine import SlotDecoder, _j
from .pages import PageCache, TokenCache

__all__ = ["MLASlotDecoder", "ExpertStats", "ExpertStepCounts"]

_NEG = -1.0e30
#: rows of the slot's view a chunk attends at a time (up-projected)
CHUNK_BLOCK_ROWS = 1024

DECODE_ROWS = registry.counter(
    "mx_serve_decode_rows_total",
    "K/V rows a decode step's attention covers, by the kind of page they "
    "lie in: `latent` (one shared row a token, the MLA family)",
    labels={"kind": "latent"})
_PAIRS_HELP = ("(token, expert) pairs the expert layers' routing chose in "
               "fetched steps: `routed`, all of them (tokens x experts a "
               "token x expert layers); `held`, those whose expert this "
               "engine holds and computes")
MOE_PAIRS = {kind: registry.counter("mx_serve_moe_pairs_total", _PAIRS_HELP,
                                    labels={"kind": kind})
             for kind in ("held", "routed")}
MOE_HIT = registry.counter(
    "mx_serve_moe_experts_hit_total",
    "distinct held experts a step's pairs fell on, summed over expert "
    "layers and fetched steps (what a step reads of the experts' weights)")


class ExpertStats:
    """What a block with an expert layer asks of its cache beside the
    attention: `valid` (the rows that are real), `step` (the kind of step
    the cache serves: ``"decode"`` or ``"chunk"``) and `count_experts`."""

    valid = None
    step = None
    expert_stats = None     # {expert layer: its int32 (held pairs, hit)}

    def count_experts(self, li, stats):
        if self.expert_stats is None:
            self.expert_stats = {}
        self.expert_stats[li] = stats


class LatentCache(ExpertStats, PageCache):
    """A page's stored form for the one latent leaf ``"c"``."""

    def _fit(self, rows):
        """Latent rows ``(..., width)`` as stored: ``(..., W)``, zeros
        after the row."""
        jnp = _j().numpy
        leaf = self.leaves["c"][0]
        pad = leaf.shape[-1] - rows.shape[-1]
        return jnp.pad(rows.astype(leaf.dtype),
                       [(0, 0)] * (rows.ndim - 1) + [(0, pad)])

    def write_pages(self, li, pages, rows):
        """Whole pages: `rows` ``(n, page_tokens, width)`` to pages `pages`."""
        pool = self.leaves["c"]
        pool[li] = pool[li].at[pages].set(self._fit(rows))

    def write_rows(self, li, wpage, woff, rows):
        """One row a slot: `rows` ``(S, width)`` to row `woff` of page
        `wpage` (whole pages out and back: a page is one contiguous block
        of the leaf, so the update runs in place)."""
        jnp = _j().numpy
        pool = self.leaves["c"]
        page = jnp.take(pool[li], wpage, axis=0)               # (S, pt, W)
        at = jnp.arange(page.shape[1])[None, :, None]
        page = jnp.where(at == woff[:, None, None],
                         self._fit(rows)[:, None, :], page)
        pool[li] = pool[li].at[wpage].set(page)

    def rows(self, li, idx):
        """Pages `idx` ``(..., n)`` of layer `li` as rows ``(..., n *
        page_tokens, W)``."""
        jnp = _j().numpy
        t = jnp.take(self.leaves["c"][li], idx, axis=0)
        return t.reshape(t.shape[:-3] + (t.shape[-3] * t.shape[-2],
                                         t.shape[-1]))


class _TokenCache(LatentCache, TokenCache):
    """One new row a slot, attended absorbed (`TokenCache`'s bookkeeping:
    where the row goes, how many rows a slot attends)."""

    step = "decode"

    def __init__(self, eng, pools, table, col, woff, rows, active):
        TokenCache.__init__(self, eng, pools, table, col, woff, rows, active)
        self.valid = active

    def attend(self, li, lp, q_nope, q_rope, latent):
        jnp = _j().numpy
        from ..ops.paged_attention import mla_decode_attention

        self.write_rows(li, self.wpage, self.woff, latent)
        pool = self.leaves["c"][li]
        dt, f32 = pool.dtype, jnp.float32
        rank = lp["w_uk"].shape[-1]
        q_lat = jnp.einsum("shn,hnc->shc", q_nope.astype(dt), lp["w_uk"],
                           preferred_element_type=f32)
        q = self._fit(jnp.concatenate([q_lat, q_rope], -1))    # (S, H, W)
        with self.eng._mesh_scope():
            o_lat = mla_decode_attention(
                q, pool, self.table, self.lengths, rank=rank,
                sm_scale=1.0 / math.sqrt(q_nope.shape[-1] + q_rope.shape[-1]))
        return jnp.einsum("shc,hcv->shv", o_lat, lp["w_uv"],
                          preferred_element_type=f32)


class _ChunkCache(LatentCache):
    """One prefill chunk of one slot, attended up-projected: the chunk's
    rows go into `chunk_pages`; its queries (positions ``t_start ..``)
    attend the slot's rows ``0 .. t_start + C - 1`` a block of pages of
    `pages_row` at a time, causally."""

    step = "chunk"

    def __init__(self, eng, pools, pages_row, chunk_pages, t_start, t_len):
        jnp = _j().numpy
        super().__init__(eng, pools)
        self.pages_row, self.chunk_pages = pages_row, chunk_pages
        self.t_start = t_start
        self.valid = jnp.arange(
            chunk_pages.shape[0] * eng.page_tokens) < t_len
        want = max(1, CHUNK_BLOCK_ROWS // eng.page_tokens)
        n = pages_row.shape[0]
        self.block_pages = max(g for g in range(1, min(want, n) + 1)
                               if n % g == 0)

    def attend(self, li, lp, q_nope, q_rope, latent):
        jax = _j()
        jnp = jax.numpy
        pt, bp = self.eng.page_tokens, self.block_pages
        t, h, _ = q_nope.shape
        self.write_pages(li, self.chunk_pages,
                         latent.reshape(t // pt, pt, latent.shape[-1]))
        pool = self.leaves["c"][li]
        dt, f32 = pool.dtype, jnp.float32
        w_uk, w_uv = lp["w_uk"], lp["w_uv"]
        rank, dr = w_uk.shape[-1], q_rope.shape[-1]
        scale = 1.0 / math.sqrt(q_nope.shape[-1] + dr)
        qn, qr = q_nope.astype(dt), q_rope.astype(dt)
        qpos = self.t_start + jnp.arange(t)
        rows = bp * pt

        def block(b, carry):
            m, l, acc = carry
            idx = jax.lax.dynamic_slice_in_dim(self.pages_row, b * bp, bp)
            blk = jnp.take(pool, idx, axis=0).reshape(rows, -1)
            c = blk[:, :rank]
            k_nope = jnp.einsum("rc,hnc->hrn", c, w_uk,
                                preferred_element_type=f32).astype(dt)
            v = jnp.einsum("rc,hcv->hrv", c, w_uv,
                           preferred_element_type=f32).astype(dt)
            s = jnp.einsum("thn,hrn->htr", qn, k_nope,
                           preferred_element_type=f32) \
                + jnp.einsum("thd,rd->htr", qr, blk[:, rank:rank + dr],
                             preferred_element_type=f32)
            seen = (b * rows + jnp.arange(rows))[None, :] <= qpos[:, None]
            s = jnp.where(seen[None], s * scale, _NEG)
            # block 0 holds position 0, which every query sees: from there
            # on `m` is a real score and a masked one's weight is exp(-1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            return (m_new, alpha * l + jnp.sum(p, axis=-1),
                    alpha[..., None] * acc + jnp.einsum(
                        "htr,hrv->htv", p.astype(dt), v,
                        preferred_element_type=f32))

        n_blocks = (self.t_start + t + rows - 1) // rows
        _, l, acc = jax.lax.fori_loop(
            0, n_blocks, block,
            (jnp.full((h, t), _NEG, f32), jnp.zeros((h, t), f32),
             jnp.zeros((h, t, w_uv.shape[-1]), f32)))
        return jnp.transpose(acc / l[..., None], (1, 0, 2))


class ExpertStepCounts:
    """For a slots class whose decoder has expert layers: their counts come
    home in the tokens' fetch. The class sets `_expert_layers`,
    `_experts_per_tok` and ``step_extra = 2 * _expert_layers + 1``."""

    def _step_out(self, tokens, cache):
        jnp = _j().numpy
        stats = cache.expert_stats or {}
        extra = [stats[li] for li in sorted(stats)]
        if len(extra) != self._expert_layers:
            raise RuntimeError(
                f"{len(extra)} expert layers counted their pairs, the "
                f"decoder has {self._expert_layers}")
        return jnp.concatenate(
            [jnp.atleast_1d(tokens)] + [e.astype(jnp.int32) for e in extra]
            + [jnp.sum(cache.valid, dtype=jnp.int32)[None]])

    def _take_extra(self, out):
        """Split a fetched array into its tokens and what `_step_out`
        appended; the counts go to the series and the step record."""
        out = onp.asarray(out)
        n = out.size - self.step_extra
        stats = out[n:-1].reshape(self._expert_layers, 2)
        held, hit = int(stats[:, 0].sum()), int(stats[:, 1].sum())
        routed = int(out[-1]) * self._experts_per_tok * self._expert_layers
        MOE_PAIRS["held"].inc(held)
        MOE_PAIRS["routed"].inc(routed)
        MOE_HIT.inc(hit)
        tracing.count(moe_pairs_held=held, moe_experts_hit=hit,
                      moe_pairs_routed=routed)
        #: the last fetched step's ``(expert layers, 2)``, for whoever
        #: watches from outside (the benchmark's runner)
        self.last_expert_stats = stats
        return out[:n]

    def fetch_tokens(self, out):
        return self._take_extra(out)

    def fetch_first(self, out):
        return int(self._take_extra(out)[0])


class MLASlotDecoder(ExpertStepCounts, SlotDecoder):
    """Paged slot decoder over a `PanguDecoder` (see the module docstring).
    Parameters as `SlotDecoder`'s; `max_len` defaults to the model's
    ``max_position_embeddings``."""

    #: latent pages are not moved between engines (prefill-only handoff,
    #: adoption): the scheduler refuses both
    page_handoff = False

    def __init__(self, source, max_slots=8, max_len=None, page_tokens=None,
                 prefill_chunk=None, n_pages=None, kv_dtype=None,
                 prefix_reuse=None, do_sample=False, top_k=None,
                 spec_k=None, draft=None):
        from ..util import env_int

        def refuse(what, why):
            raise NotImplementedError(
                f"the pangu_moe family is not served with {what}: {why}")

        if spec_k is None:
            spec_k = env_int("MXNET_SERVE_SPEC_K", 0)
        if spec_k or draft is not None:
            refuse("speculative decoding (spec_k > 0, draft)",
                   "verify and draft programs exist for the GPT block only, "
                   "and the release's multi-token-prediction module is not "
                   "held (ROADMAP R7)")
        if kv_dtype is None:
            kv_dtype = os.environ.get("MXNET_SERVE_KV_DTYPE", "fp")
        if kv_dtype != "fp":
            refuse(f"kv_dtype={kv_dtype!r}",
                   "what a per-page int8 scale does to a row that is a "
                   "normed latent beside a rotated key is not worked out")
        super().__init__(source, max_slots=max_slots, max_len=max_len,
                         page_tokens=page_tokens, prefill_chunk=prefill_chunk,
                         n_pages=n_pages, kv_dtype="fp",
                         prefix_reuse=prefix_reuse, do_sample=do_sample,
                         top_k=top_k, spec_k=0)
        self.chunk_buckets = self._quarter_and_whole_buckets()
        self._expert_layers = self._dec.expert_layers
        self._experts_per_tok = self._dec.config.num_experts_per_tok
        # a step's (held pairs, held experts hit) an expert layer, and
        # the rows that were routed
        self.step_extra = 2 * self._expert_layers + 1
        ref = weakref.ref(self)
        registry.register_pull_gauge(
            "mx_serve_pages_in_use",
            lambda: None if ref() is None else ref().allocator.used_pages,
            "pool pages the slots hold, by kind (latent: the MLA family)",
            labels={"kind": "latent"})

    def _resolve_decoder(self, source):
        if not isinstance(source, PanguDecoder):
            raise TypeError("MLASlotDecoder needs a PanguDecoder, got "
                            f"{type(source).__name__}")
        return source

    # -- the cache objects ----------------------------------------------------

    def _token_cache(self, pools, table, pos, active):
        return _TokenCache(self, pools, table, *self._row_of(pos), active)

    def _chunk_cache(self, pools, pages, t_start, t_len):
        return _ChunkCache(self, pools, *pages, t_start, t_len)

    def _count_rows(self, at):
        DECODE_ROWS.inc(int((at + 1).sum()))
        return super()._count_rows(at)

    # -- debug / tests --------------------------------------------------------

    def slot_kv(self, slot, n_tokens):
        """Host copy of a slot's first `n_tokens` latent rows, every layer:
        ``(L, n_tokens, width)`` float32."""
        jnp = _j().numpy
        self._ensure_pool()
        cache = LatentCache(self, self._pools)
        idx = jnp.asarray(self._table[slot])
        width = self._dec.kv_geometry()[2]
        return onp.asarray(jnp.stack(
            [cache.rows(li, idx)[:n_tokens, :width]
             for li in range(len(self._pools["c"]))]), onp.float32)
