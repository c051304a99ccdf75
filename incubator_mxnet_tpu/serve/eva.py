"""`mx.serve` for the EvaByte family: two kinds of page in one pool.

A slot of `EvaSlotDecoder` holds, in the pool and allocator every family
shares,

- **window pages**: the exact K/V rows of the window its position stands
  in, ``window / page_tokens`` pages at most (128 at the published sizes),
  mapped as the positions are reached, and
- **summary pages**: one K/V row per finished chunk of every finished
  window (`models.evabyte.summarize`), ``window / chunk / page_tokens`` pages
  a window (8).

Its row of the page table is ``summary pages ++ window pages`` and the row
count ``summary rows + (t mod window) + 1``: EVA's one softmax over window
rows and summaries is ordinary attention over the concatenated rows, so
decode is `ops.paged_attention.paged_decode_attention` as it stands (the
kernel ``mx_paged_decode`` on the chip) and no kernel is new.

**The roll.** When a slot's next position is a multiple of `window`, the
program ``mx_eva_roll`` reads the finished window's pages and writes its
summary pages (`roll_step`; the scheduler takes the summary pages from the
allocator before and gives the window's back after). In prefill it runs
between two chunks (`prefill_chunk` divides `window`, chunks start at its
multiples, so none straddles a roll), in decode before the step that needs
it; both inside the step, as span ``mx.serve.eva.roll`` and step-record
field ``eva_roll``.

The programs are `SlotDecoder`'s own (one prefill-chunk and one decode
skeleton for every family, `serve/engine.py`), run over this family's block,
`models.evabyte.EvaByteDecoder.layer`. What is brought here is what is the
family's: the page arithmetic (`pages_needed`, `pages_at`, `_table_width`,
`_row_of`: where a position's row lies in ``summary pages ++ window pages`` and
how many rows it attends; `_count_rows`), a chunk's cache access (`_chunk_pages`
on the host, `_ChunkCache` traced: attention over summary and window rows;
decode's is the shared `pages.TokenCache`), the roll, and the refusals.

Not served for this family, each refused with `NotImplementedError`:
speculative decoding (``spec_k > 0``), int8 pages, prefix reuse (what may be
kept of a window that rolled is an open question, ROADMAP R4), prefill-only
handoff and the sharded engine.
"""
from __future__ import annotations

import math
import os
import weakref

import numpy as onp

from ..models.evabyte import EvaByteDecoder, summarize
from ..telemetry import registry, tracing
from .engine import SlotDecoder, _j
from .pages import PageCache

__all__ = ["EvaSlotDecoder"]

ROLLS = registry.counter(
    "mx_serve_eva_rolls_total",
    "finished windows turned into summary pages (prefill and decode)")
_ROWS_HELP = ("K/V rows a decode step's attention covers, by the kind of "
              "page they lie in: `window` (exact rows of the slot's current "
              "window) and `summary` (one row a finished chunk)")
DECODE_ROWS = {kind: registry.counter("mx_serve_decode_rows_total",
                                      _ROWS_HELP, labels={"kind": kind})
               for kind in ("window", "summary")}


class _ChunkCache(PageCache):
    """Cache access of one prefill chunk of one slot: the chunk's rows go
    into its pages, and its queries attend every summary row (no mask
    beyond their count) and the window's rows up to themselves."""

    def __init__(self, eng, pools, sum_pages, n_sum, win_pages, chunk_pages,
                 r0):
        super().__init__(eng, pools)
        self.sum_pages, self.n_sum = sum_pages, n_sum
        self.win_pages, self.chunk_pages, self.r0 = win_pages, chunk_pages, r0

    def attend(self, li, q, k, v):
        jax = _j()
        jnp = jax.numpy
        pt = self.eng.page_tokens
        t, h, d = q.shape
        dt = self.leaves["k"][li].dtype

        def to_pages(x):           # (T, H, d) -> (T / pt, H, pt, d)
            return jnp.transpose(x.astype(dt).reshape(t // pt, pt, h, d),
                                 (0, 2, 1, 3))

        self.write_pages(li, self.chunk_pages, to_pages(k), to_pages(v))
        ks, vs = self.rows(li, self.sum_pages)
        kw, vw = self.rows(li, self.win_pages)
        qd = q.astype(dt)
        s_r = jnp.einsum("thd,hjd->htj", qd, ks,
                         preferred_element_type=jnp.float32)
        s_l = jnp.einsum("thd,hmd->htm", qd, kw,
                         preferred_element_type=jnp.float32)
        remote = jnp.arange(ks.shape[1])[None, :] < self.n_sum
        local = jnp.arange(kw.shape[1])[None, :] \
            <= self.r0 + jnp.arange(t)[:, None]
        neg = jnp.float32(-1e30)
        s = jnp.concatenate([jnp.where(remote[None], s_r, neg),
                             jnp.where(local[None], s_l, neg)], -1)
        p = jax.nn.softmax(s / math.sqrt(d), axis=-1).astype(dt)
        n_r = ks.shape[1]
        return (jnp.einsum("htj,hjd->thd", p[..., :n_r], vs,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("htm,hmd->thd", p[..., n_r:], vw,
                             preferred_element_type=jnp.float32))


class EvaSlotDecoder(SlotDecoder):
    """Paged slot decoder over an `EvaByteDecoder` (see the module
    docstring). Parameters as `SlotDecoder`'s; `max_len` defaults to the
    model's ``max_position_embeddings``."""

    lazy_pages = True

    def __init__(self, source, max_slots=8, max_len=None, page_tokens=None,
                 prefill_chunk=None, n_pages=None, kv_dtype=None,
                 prefix_reuse=None, do_sample=False, top_k=None,
                 spec_k=None, draft=None):
        from ..util import env_int

        def refuse(what, why):
            raise NotImplementedError(
                f"the evabyte family is not served with {what}: {why}")

        if spec_k is None:
            spec_k = env_int("MXNET_SERVE_SPEC_K", 0)
        if spec_k or draft is not None:
            refuse("speculative decoding (spec_k > 0, draft)",
                   "verify and draft programs exist for the GPT block only "
                   "(drafting with the model's own prediction heads: "
                   "ROADMAP R7)")
        if kv_dtype is None:
            kv_dtype = os.environ.get("MXNET_SERVE_KV_DTYPE", "fp")
        if kv_dtype != "fp":
            refuse(f"kv_dtype={kv_dtype!r}",
                   "summary rows are made from stored rows, and what a "
                   "per-page int8 scale does to them is not worked out")
        if prefix_reuse:
            refuse("prefix_reuse=True",
                   "a window that rolled keeps summaries, not rows, and "
                   "what the prefix cache may keep of it is open (ROADMAP R4)")
        cfg = source.config
        self.window = int(cfg.window_size)
        self.chunk = int(cfg.chunk_size)
        super().__init__(source, max_slots=max_slots, max_len=max_len,
                         page_tokens=page_tokens, prefill_chunk=prefill_chunk,
                         n_pages=n_pages, kv_dtype="fp", prefix_reuse=False,
                         do_sample=do_sample, top_k=top_k, spec_k=0)
        if self.window % self.prefill_chunk:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must divide the "
                f"window ({self.window}): no chunk may straddle a roll")
        self.chunk_buckets = self._quarter_and_whole_buckets()
        self._held = onp.zeros(self.max_slots, onp.int64)    # pages a slot
        self._rolled = onp.zeros(self.max_slots, onp.int64)  # windows done
        self._roll_jit = None
        ref = weakref.ref(self)

        def in_use(kind):
            def probe():
                eng = ref()
                if eng is None:
                    return None
                summary = int(eng._rolled.sum()) * eng.summary_pages
                return summary if kind == "summary" \
                    else int(eng._held.sum()) - summary
            return probe

        for kind in ("window", "summary"):
            registry.register_pull_gauge(
                "mx_serve_pages_in_use", in_use(kind),
                "pool pages the slots hold, by kind (evabyte family)",
                labels={"kind": kind})

    def _resolve_decoder(self, source):
        if not isinstance(source, EvaByteDecoder):
            raise TypeError("EvaSlotDecoder needs an EvaByteDecoder, got "
                            f"{type(source).__name__}")
        return source

    # -- page arithmetic ------------------------------------------------------

    def _table_width(self):
        pt, w, c = self.page_tokens, self.window, self.chunk
        if w % pt or w % c or (w // c) % pt:
            raise ValueError(
                f"window ({w}) must hold whole pages ({pt}) of rows and of "
                f"chunk summaries (chunk {c})")
        self.window_pages = w // pt               # exact rows of a window
        self.summary_pages = w // c // pt         # its chunks' summaries
        self.max_windows = -(-self.max_len // w)
        return self.summary_pages * (self.max_windows - 1) + self.window_pages

    def pages_at(self, n):
        """Pages a slot holds with positions ``0 .. n-1`` mapped: the
        summaries of the windows before position ``n - 1``'s, and that
        window's pages up to it."""
        n = int(n)
        w = (n - 1) // self.window
        return self.summary_pages * w + -(-(n - w * self.window)
                                          // self.page_tokens)

    def pages_needed(self, n):
        """The most pages a request of ``n`` positions holds at once. With
        ``f = (n - 1) // window`` finished windows at its end: the ``f``
        windows' summaries, and beside them the window pages — a whole
        window's while the last of them is rolled (the new summary pages are
        taken before the window's are given back), and never more than
        ``ceil(n / page_tokens)``::

            summary_pages * f + min(window_pages, ceil(n / page_tokens))
        """
        n = int(n)
        f = (n - 1) // self.window
        return self.summary_pages * f + min(self.window_pages,
                                            -(-n // self.page_tokens))

    def rolls_before(self, pos):
        """True where position `pos` opens a window: the window before it
        is rolled before a row is written at `pos`."""
        return pos > 0 and pos % self.window == 0

    def window_end(self, pos):
        """One past the last position of `pos`'s window."""
        return (pos // self.window + 1) * self.window

    def _row_of(self, pos):
        """As `SlotDecoder._row_of`: a position's row lies in its window's
        pages, after the summary pages of the windows before, and attends
        their summary rows and its window's rows up to itself."""
        pt = self.page_tokens
        w, r = pos // self.window, pos % self.window
        return (self.summary_pages * w + r // pt, r % pt,
                (self.window // self.chunk) * w + r + 1)

    def _count_rows(self, at):
        rows_s = int((at // self.window).sum()) * (self.window // self.chunk)
        rows_w = int((at % self.window + 1).sum())
        DECODE_ROWS["window"].inc(rows_w)
        DECODE_ROWS["summary"].inc(rows_s)
        return int((-(-(at % self.window + 1) // self.page_tokens)).sum()) \
            + rows_s // self.page_tokens

    # -- page table -----------------------------------------------------------

    def set_slot_pages(self, slot, pages):
        super().set_slot_pages(slot, pages)
        self._held[slot] = len(pages)

    def clear_slot(self, slot):
        super().clear_slot(slot)
        self._held[slot] = self._rolled[slot] = 0

    # -- the roll -------------------------------------------------------------

    def _build_roll(self):
        jax = _j()
        jnp = jax.numpy
        c, pt, n_sum = self.chunk, self.page_tokens, self.summary_pages

        def mx_eva_roll(features, pools, win_pages, new_pages):
            with jax.named_scope("mx_eva_roll"):
                cache = PageCache(self, pools)
                for li, (phi, mu) in enumerate(features):
                    k, v = cache.rows(li, win_pages)           # (H, W, d)
                    h, w, d = k.shape
                    kh, vh = summarize(k.reshape(h, w // c, c, d),
                                       v.reshape(h, w // c, c, d),
                                       phi[:, None, :], mu[:, None, :])
                    cache.write_pages(li, new_pages, *(
                        jnp.transpose(rows.reshape(h, n_sum, pt, d),
                                      (1, 0, 2, 3)) for rows in (kh, vh)))
                return cache.pools()

        return self._observed(mx_eva_roll, "eva_roll")

    def roll_step(self, slot, win_pages, new_pages):
        """Turn `slot`'s finished window (pool pages `win_pages`, whole and
        in order) into summary rows in `new_pages`. Launched, not waited
        for: the program that next reads the pool runs after it."""
        jnp = _j().numpy
        with tracing.phase("mx.serve.eva.roll", "eva_roll") as launch:
            self._ensure_pool()
            if self._roll_jit is None:
                self._roll_jit = self._build_roll()
            if len(win_pages) != self.window_pages \
                    or len(new_pages) != self.summary_pages:
                raise ValueError(
                    f"a roll takes {self.window_pages} window pages and "
                    f"{self.summary_pages} new ones, got {len(win_pages)} "
                    f"and {len(new_pages)}")
            features = tuple((lp["phi"], lp["mu"])
                             for lp in self._dec._params["layers"])
            pages = (jnp.asarray(win_pages, jnp.int32),
                     jnp.asarray(new_pages, jnp.int32))
            launch.site()
            self._pools = self._roll_jit(features, self._pools, *pages)
            self._rolled[slot] += 1
            ROLLS.inc()

    # -- a prefill chunk's cache access (`SlotDecoder._build_prefill`) --------

    def _chunk_pages(self, slot, t_start, bucket):
        """As `SlotDecoder._chunk_pages`; `t_start` is a multiple of
        `prefill_chunk`, and the chunk's window pages are mapped (the
        scheduler saw to both)."""
        jnp = _j().numpy
        pt = self.page_tokens
        if t_start % self.prefill_chunk:
            raise ValueError(
                f"chunk start {t_start} is not a multiple of "
                f"prefill_chunk ({self.prefill_chunk})")
        row = self._table[slot].copy()    # the launch's own
        n_sum = self.summary_pages * (t_start // self.window)
        sum_pages = onp.zeros(max(1, self.pages_per_slot
                                  - self.window_pages), onp.int32)
        sum_pages[:n_sum] = row[:n_sum]
        win_pages = row[n_sum:n_sum + self.window_pages]
        first_page = (t_start % self.window) // pt
        chunk_pages = onp.zeros(bucket // pt, onp.int32)
        avail = win_pages[first_page:first_page + bucket // pt]
        chunk_pages[:avail.size] = avail
        return (jnp.asarray(sum_pages), jnp.int32(n_sum * pt),
                jnp.asarray(win_pages), jnp.asarray(chunk_pages))

    def _chunk_cache(self, pools, pages, t_start, t_len):  # noqa: ARG002
        return _ChunkCache(self, pools, *pages, t_start % self.window)
