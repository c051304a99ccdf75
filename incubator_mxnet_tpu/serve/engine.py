"""Paged slot-cache compiled decode programs (the device half of `mx.serve`).

A **paged** KV pool, the vLLM/PagedAttention block-allocation idea
re-expressed TPU-natively (static shapes, gather-by-page-table, zero
steady-state recompiles): a slot holds the pages its request has reached,
not ``max_len`` rows of HBM, and a shared prompt prefix is prefilled once:

- **page pool** — one persistent device array per K and V **per
  layer**: a tuple of L arrays of ``(n_pages, H, page_tokens, d)``
  values (a float leaf keeps a head's ``(page_tokens, d)`` plane packed
  to 128 lanes, ``(page_tokens * d // 128, 128)``, where the head is
  narrower: no padding, and a page is one contiguous block of the leaf —
  `ops.paged_attention`, "a page as it is stored"). Page 0 is a
  reserved *trash* page: unallocated page-table
  entries and inactive-slot writes land there, and its contents are
  never attended (the validity mask excludes them before softmax).
  The per-layer split is load-bearing for cost, not cosmetics: with a
  single stacked ``(L, ...)`` array threaded through a
  ``lax.scan``-over-layers, XLA re-stacks the scan's per-layer outputs
  into a FRESH pool buffer every step — ``memory_analysis`` temp bytes
  ~ the whole pool, i.e. per-token cost O(L × n_pages). With per-layer
  leaves and a Python-unrolled layer loop, every leaf aliases its
  donated input in place (``input_output_alias`` covers all 2L pool
  leaves) and a step's temp bytes are O(active slots × page) — the
  compile ledger (`telemetry.compiles`) records both facts per
  program. The layout is also the pod-sharding-friendly one: each leaf
  can carry its own `PartitionSpec` (heads sharded, pages replicated)
  without resharding a fused 5-D array.
- **page table** — a host-side ``(max_slots, pages_per_slot)`` int32
  array mapping each slot's token range to pool pages (mirrored to the
  device lazily, refreshed only when allocation changes). Decode's
  attention (`ops.paged_attention.paged_decode_attention`) goes through
  it to the pages under each slot's ``pos`` and to no others (the kernel
  is handed the table itself and copies a live page at a time); prefill
  writes whole pages with a static-shape scatter and gathers ONE slot's
  logical view with a static-shape ``jnp.take`` over its table row.
- **allocator + prefix cache** — `PageAllocator` (host-only free list +
  refcounts; OOM raises the loud `PagePoolExhausted`, nothing is ever
  silently evicted while referenced) and `PrefixCache` (hash of the
  page-aligned token prefix → page list). A common system prompt is
  prefilled once and its pages attached read-only to every later request
  with the same prefix; "copy-on-extend" is structural: a request only
  ever *writes* pages past its shared prefix (partial tail pages are
  re-prefilled privately, and decode's first write position provably
  lands beyond every shared page), so shared pages need no copies and no
  write-protection machinery.

**One skeleton per kind of step, whatever the family and the page format.**
`SlotDecoder` is family-free. Each program embeds its rows, runs the
decoder's block layer by layer (`_run_layers`), samples, and returns the
pools; the block (`GPTDecoder.layer`, `EvaByteDecoder.layer`) is written once
per family against a **cache-access object** (`serve/pages.py`)::

    cache.attend(li, q, k, v) -> o

which writes the rows ``k, v`` of layer ``li`` into the pool and returns the
attention of ``q`` over what the cache holds for them. The cache objects
alone know what a stored page is (float packed to 128 lanes, or int8 with a
scale per (page, head)); the pools travel through every program as ONE
donated pytree argument, so a program has one signature and one jitted
wrapper (`_observed`) whatever the format. A new family brings a decoder with
``embed`` / ``layer_params`` / ``layer`` / ``next_logits`` / ``kv_geometry``
/ ``layer_kinds`` (what each layer keeps in a slot: ``"pages"``, ``"state"``
or nothing — how many layers there are, and `kv_geometry()[0]` of them hold
pages) and a slots subclass with its page arithmetic (`pages_needed`,
`pages_at`, `_table_width`, `_row_of`, `_count_rows`) and its chunk's cache
access
(`_chunk_pages`, `_chunk_cache`): `serve/eva.py` is one.

Two compiled program families in the base configuration:

- **chunked prefill** (one program per chunk-length bucket,
  `models.decoding.chunk_buckets`): one page-aligned chunk of ONE
  request's prompt — embeds the chunk at its true positions (traced
  ``t_start``), writes the chunk's K/V pages into the pool, attends the
  chunk's queries against the slot's gathered view (prefix pages +
  itself) under a causal-with-offset mask (`pages.ChunkCache`), and samples
  a first token from the chunk's last real row (used by the host only on
  the final chunk). Splitting long prompts into chunks lets the scheduler
  interleave decode steps between chunks, so a long-prompt arrival no
  longer stalls every running request for a whole monolithic prefill.
- **decode** (ONE program): one token for ALL slots — per-slot write
  of the new K/V at the page and offset `_row_of` names (inactive slots
  are redirected to the trash page), attention over each decoding
  slot's live pages (`pages.TokenCache`), per-slot sampling. The attention
  is one op with two implementations, chosen from what the process
  observes and counted in
  ``mx_kernel_dispatch_total{op="paged_decode_attention",impl=}``: on one
  TPU device with float pools the pallas kernel ``mx_paged_decode``,
  which copies the pages below ``pos`` straight from the layer's pool
  leaf, a block of them a grid step, and never builds the ``(S, H,
  max_len, d)`` view (its two products on the MXU where a bfloat16 head
  fills the 128 lanes, on the VPU in exact float32 otherwise:
  ``mx_kernel_dispatch_total{op="paged_decode_products",impl=}``); on the CPU,
  under a multi-device mesh and for int8 pools the XLA expression —
  gather every slot's whole view through the table, mask, softmax, two
  einsums.
  ``mx_serve_decode_pages_total{kind="live"|"view"}`` says what share of
  that view a step's attention covers.

With **speculative decoding** armed (``spec_k > 0``; the GPT block only),
decode is replaced by two more families that advance up to ``k + 1`` tokens
per round instead of one per launch:

- **verify** (ONE program): the target model runs ``k + 1`` token rows
  for ALL slots in one batched pass (`pages.RowsCache`) — row ``i``
  consumes ``[last, d_1..d_k][i]`` at position ``pos + i``, writes its K/V
  to the slot's pages (beyond-budget rows are redirected to the trash
  page) and emits the greedy next token. Because row ``i`` only
  attends positions ``<= pos + i``, the batched pass is mathematically
  identical to ``k + 1`` sequential decode steps — the same identity
  chunked prefill already relies on — which is what makes greedy spec
  decode token-for-token equal to the non-spec engine.
- **draft** (ONE program, model drafts only): ``k`` unrolled greedy
  decode steps of the small draft model against its OWN pools
  (same page table and allocator, so draft pages track target pages
  exactly). The ``draft="ngram"`` fallback drafts on the host
  (`models.decoding.NgramProposer`) and adds NO device program.

Acceptance runs on host numpy in the scheduler: the longest drafted
prefix matching the verify row outputs commits (plus the bonus token
from the first mismatching row), and pages speculatively extended for
rejected suffixes roll back through `PageAllocator.decref`.

All families donate the pools so XLA updates them in place. Optional
**int8 KV** (``MXNET_SERVE_KV_DTYPE=int8``) stores each layer's pool as
int8 with one scale per (page, head), halving resident KV bytes per slot;
decode re-quantizes only the single page it writes (grow-only per-page
scale).

Stale-row safety (unchanged argument, now per page): position ``p`` of a
slot only enters the attention mask once the slot's ``pos`` reaches
``p``, and the program that advances ``pos`` to ``p`` writes ``p``'s K/V
first — so a freed-and-reused page's previous contents, chunk padding,
and generation headroom are all dead by construction.
"""
from __future__ import annotations

import contextlib
import hashlib
import os
import weakref

import numpy as onp

from ..models.decoding import (GPTDecoder, NgramProposer, bucket_chunk,
                               chunk_buckets)
from ..telemetry import compiles as _compiles
from ..telemetry import hbm as _hbm
from ..telemetry import registry, tracing
from .pages import (ChunkCache, PageCache, RowsCache, TokenCache,
                    make_pools, page_bytes)

__all__ = ["SlotDecoder", "PageAllocator", "PrefixCache",
           "PagePoolExhausted", "DEFAULT_PAGE_TOKENS",
           "DEFAULT_PREFILL_CHUNK"]

#: Tokens per KV page (MXNET_SERVE_PAGE_TOKENS). Smaller pages pack
#: tighter and share more; larger pages shrink the page table and the
#: gather fan-in.
DEFAULT_PAGE_TOKENS = 16
#: Prefill chunk ceiling in tokens (MXNET_SERVE_PREFILL_CHUNK); must be
#: a multiple of the page size (rounded up if not).
DEFAULT_PREFILL_CHUNK = 64

PAD_TOKENS = registry.counter(
    "mx_decode_bucket_pad_tokens_total",
    "prompt tokens added by pad-to-bucket in the decode/serving "
    "path (padding waste)")


_PAGES_HELP = ("KV pages a decode step's attention covers: `live`, the "
               "pages under the decoding slots' positions (what the paged "
               "kernel reads); `view`, max_slots x pages_per_slot (what a "
               "gathered view of every slot holds)")
DECODE_PAGES = {kind: registry.counter("mx_serve_decode_pages_total",
                                       _PAGES_HELP, labels={"kind": kind})
                for kind in ("live", "view")}


def _j():
    import jax

    return jax


def _upload(host, dtype=None):
    """A launch's own copy of a host array, on the device. The launch may
    still read it when the host's array has changed: a program is queued,
    not waited for, and an upload need not have left the host's buffer
    when the call returns (on the CPU it never does)."""
    return _j().numpy.asarray(onp.array(host, dtype))


class PagePoolExhausted(RuntimeError):
    """The KV page pool cannot satisfy an allocation — loud, like
    `QueueFull`: pages referenced by live requests or the prefix cache
    are NEVER silently evicted to make room. Shed load, shrink
    max_new_tokens, raise ``n_pages``, or let running requests retire."""


class PageAllocator:
    """Host-side page accounting for the paged KV pool.

    Pure bookkeeping — it never touches device memory. Page 0 is
    reserved as the trash page (write target for inactive slots and
    padding; never allocated, never read through a mask). Shared pages
    are reference-counted: a page returns to the free list only when its
    LAST reference (requests + prefix-cache entries) drops it.
    """

    def __init__(self, n_pages, page_tokens):
        if n_pages < 2:
            raise ValueError(f"n_pages must be >= 2 (page 0 is reserved), "
                             f"got {n_pages}")
        self.n_pages = int(n_pages)
        self.page_tokens = int(page_tokens)
        # LIFO free list: hot pages get reused while their tiles are warm
        self._free = list(range(self.n_pages - 1, 0, -1))
        self._ref = onp.zeros(self.n_pages, onp.int64)

    @property
    def usable_pages(self):
        """Allocatable pages (total minus the reserved trash page)."""
        return self.n_pages - 1

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def used_pages(self):
        """Pages currently referenced — shared pages counted ONCE."""
        return self.usable_pages - len(self._free)

    def refcount(self, page):
        return int(self._ref[page])

    def alloc(self, n):
        """Take `n` fresh pages (refcount 1 each). Raises the loud
        `PagePoolExhausted` when the pool cannot satisfy the request —
        the caller decides whether to evict unused prefix-cache entries
        and retry, or to keep the request queued."""
        n = int(n)
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise PagePoolExhausted(
                f"KV page pool exhausted: need {n} pages, "
                f"{len(self._free)}/{self.usable_pages} free "
                f"({self.used_pages} referenced by live requests or the "
                "prefix cache) — shed load, raise n_pages, or wait for "
                "running requests to retire; shared pages are never "
                "silently evicted")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def incref(self, pages):
        for p in pages:
            if self._ref[p] <= 0:
                raise RuntimeError(
                    f"incref on free page {p} — a shared page was dropped "
                    "while still mapped (allocator bookkeeping bug)")
            self._ref[p] += 1

    def decref(self, pages):
        """Release one reference per page; pages whose count reaches zero
        return to the free list."""
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
            elif self._ref[p] < 0:
                raise RuntimeError(
                    f"double free of page {p} (refcount went negative) — "
                    "allocator bookkeeping bug")


class _PrefixEntry:
    __slots__ = ("pages", "tokens", "last_used")

    def __init__(self, pages, tokens, last_used):
        self.pages = pages
        self.tokens = tokens
        self.last_used = last_used


class PrefixCache:
    """Shared-prefix page reuse: hash(page-aligned token prefix) → pages.

    Entries hold their OWN page references, so a cached prefix outlives
    the request that prefilled it; `evict_unused` drops
    least-recently-used entries (their references only — pages still
    mapped into live requests stay allocated, which is the "no silent
    eviction of shared pages" contract).

    Every page boundary of a registered prompt gets its own entry, so a
    later prompt matching any page-aligned prefix reuses the longest
    match. Lookups always leave ≥ 1 prompt token uncovered: the final
    token must run through prefill compute to produce the first sampled
    token.
    """

    def __init__(self, allocator, enabled=True):
        self._alloc = allocator
        self._entries = {}
        self._clock = 0
        self.enabled = bool(enabled)

    def __len__(self):
        return len(self._entries)

    @property
    def cached_pages(self):
        """Pages referenced by at least one cache entry (counted once)."""
        seen = set()
        for e in self._entries.values():
            seen.update(e.pages)
        return len(seen)

    def _page_digests(self, prompt, n_pages):
        """Rolling blake2b digest at each of the first `n_pages` page
        boundaries of `prompt` (one pass over the token bytes)."""
        pt = self._alloc.page_tokens
        arr = onp.ascontiguousarray(onp.asarray(prompt, onp.int32))
        h = hashlib.blake2b(digest_size=16)
        out = []
        for jj in range(n_pages):
            h.update(arr[jj * pt:(jj + 1) * pt].tobytes())
            out.append(h.digest())
        return out

    def shared_tokens(self, prompt):
        """Length of the longest cached page-aligned proper prefix of
        `prompt`, in tokens (0 when nothing matches). Read-only probe —
        no LRU touch — for the scheduler's remaining-chunk SJF key."""
        tokens, _ = self._match(prompt, touch=False)
        return tokens

    def lookup(self, prompt):
        """Longest cached page-aligned proper prefix → ``(tokens,
        pages)``. Does NOT take page references — the caller increfs the
        returned pages if (and only if) it maps them into a request."""
        return self._match(prompt, touch=True)

    def _match(self, prompt, touch):
        if not self.enabled or not self._entries:
            return 0, []
        pt = self._alloc.page_tokens
        max_pages = (len(prompt) - 1) // pt
        if max_pages < 1:
            return 0, []
        digests = self._page_digests(prompt, max_pages)
        for jj in range(max_pages, 0, -1):
            e = self._entries.get(digests[jj - 1])
            if e is not None:
                if touch:
                    self._clock += 1
                    e.last_used = self._clock
                return jj * pt, list(e.pages)
        return 0, []

    def register(self, prompt, pages):
        """Make the prompt's full pages shareable. `pages` is the
        request's page list (its prefill must be COMPLETE — the pool
        holds valid K/V for every full prompt page). Returns the number
        of new entries. Idempotent per prefix."""
        if not self.enabled:
            return 0
        pt = self._alloc.page_tokens
        n_full = len(prompt) // pt
        if n_full < 1:
            return 0
        digests = self._page_digests(prompt, n_full)
        added = 0
        for jj in range(1, n_full + 1):
            d = digests[jj - 1]
            if d in self._entries:
                continue
            entry_pages = tuple(int(p) for p in pages[:jj])
            self._alloc.incref(entry_pages)
            self._clock += 1
            self._entries[d] = _PrefixEntry(entry_pages, jj * pt,
                                            self._clock)
            added += 1
        return added

    def evict_unused(self, pages_needed):
        """Drop least-recently-used entries until at least `pages_needed`
        pages are free or no entries remain. Only cache references are
        dropped: a page still mapped into a live request keeps a nonzero
        refcount and is NEVER reused from under it. Returns entries
        dropped."""
        if self._alloc.free_pages >= pages_needed:
            return 0
        dropped = 0
        for d, e in sorted(self._entries.items(),
                           key=lambda kv: kv[1].last_used):
            if self._alloc.free_pages >= pages_needed:
                break
            self._alloc.decref(e.pages)
            del self._entries[d]
            dropped += 1
        if dropped:
            registry.counter(
                "mx_serve_prefix_evictions_total",
                "prefix-cache entries dropped to free pages (cache refs "
                "only — live requests keep their pages)").inc(dropped)
        return dropped

    def clear(self):
        for e in self._entries.values():
            self._alloc.decref(e.pages)
        self._entries.clear()


class SlotDecoder:
    """Paged slot-cache decoder: the family-free programs (module
    docstring), here over a `GPTDecoder` (or the `GPTModel`-shaped Block it
    wraps) with pages mapped by position; `serve.eva.EvaSlotDecoder`
    subclasses it for its family.

    Parameters
    ----------
    source : GPTDecoder or Block
        The model to serve.
    max_slots : int
        Static batch width of the decode program.
    max_len : int
        Per-slot sequence capacity (prompt + generated); defaults to the
        model's position-embedding length.
    page_tokens : int
        Tokens per KV page (default ``MXNET_SERVE_PAGE_TOKENS`` or 16).
    prefill_chunk : int
        Prefill chunk ceiling in tokens (default
        ``MXNET_SERVE_PREFILL_CHUNK`` or 64); rounded up to a multiple
        of `page_tokens` and capped at the slot view.
    n_pages : int
        Total pool pages INCLUDING the reserved trash page 0. Defaults
        to full backing for every slot (``max_slots * pages_per_slot``
        + 1); smaller values trade HBM for admission pressure
        (`PagePoolExhausted` is the loud limit).
    kv_dtype : "fp" | "int8"
        KV storage (default ``MXNET_SERVE_KV_DTYPE`` or the parameter
        dtype). int8 halves resident KV bytes with one scale per
        (layer, page, head).
    prefix_reuse : bool
        Arm the shared-prefix cache (default: on where the family has
        one).
    do_sample / top_k : sampling mode, STATIC per engine; `temperature`
        stays a runtime per-request argument.
    spec_k : int
        Speculative decoding draft length (default
        ``MXNET_SERVE_SPEC_K`` or 0 = off). Greedy engines only
        (``do_sample=False``): greedy verification is what makes spec
        output token-for-token identical to plain decode.
    draft : "ngram" | GPTDecoder | Block
        Draft source when ``spec_k > 0`` (default
        ``MXNET_SERVE_SPEC_DRAFT`` or ``"ngram"``): the host n-gram
        proposer, or a small GPT whose vocabulary matches the target
        (drafted ids index the target embedding).
    """

    #: pages are mapped by position, all of a request's at admission; a
    #: family whose slots take and free pages as they go says True
    #: (`serve/eva.py`) and the scheduler maps positions before it runs them
    lazy_pages = False

    def __init__(self, source, max_slots=8, max_len=None, page_tokens=None,
                 prefill_chunk=None, n_pages=None, kv_dtype=None,
                 prefix_reuse=None, do_sample=False, top_k=None,
                 spec_k=None, draft=None):
        self._dec = self._resolve_decoder(source)
        if prefix_reuse is None:
            prefix_reuse = True
        model_max = self._dec._max_length
        self.max_len = int(max_len) if max_len is not None else model_max
        if self.max_len > model_max:
            raise ValueError(
                f"max_len ({self.max_len}) exceeds the model's position "
                f"table ({model_max})")
        self.max_slots = int(max_slots)
        if self.max_slots < 1:
            raise ValueError("max_slots must be >= 1")

        from ..util import env_int

        pt = int(page_tokens) if page_tokens is not None else \
            env_int("MXNET_SERVE_PAGE_TOKENS", DEFAULT_PAGE_TOKENS)
        if pt < 1:
            raise ValueError(f"page_tokens must be >= 1, got {pt}")
        self.page_tokens = pt
        self.view_tokens = -(-self.max_len // pt) * pt        # ceil
        self.pages_per_slot = self._table_width()
        chunk = int(prefill_chunk) if prefill_chunk is not None else \
            env_int("MXNET_SERVE_PREFILL_CHUNK", DEFAULT_PREFILL_CHUNK)
        chunk = max(pt, -(-chunk // pt) * pt)                 # page-align up
        self.prefill_chunk = min(chunk, self.view_tokens)
        self.chunk_buckets = chunk_buckets(pt, self.prefill_chunk)

        if kv_dtype is None:
            kv_dtype = os.environ.get("MXNET_SERVE_KV_DTYPE", "fp")
        if kv_dtype not in ("fp", "int8"):
            raise ValueError(
                f"kv_dtype must be 'fp' or 'int8', got {kv_dtype!r} "
                "(MXNET_SERVE_KV_DTYPE)")
        self.kv_dtype = kv_dtype

        default_pages = self.max_slots * self.pages_per_slot + 1
        self.n_pages = int(n_pages) if n_pages is not None else default_pages
        self.allocator = PageAllocator(self.n_pages, pt)
        self.prefix_cache = PrefixCache(self.allocator,
                                        enabled=bool(prefix_reuse))
        registry.register_pull_gauge(
            "mx_serve_page_occupancy",
            _occupancy_probe(self.allocator),
            "fraction of usable KV pool pages referenced (shared pages "
            "counted once) [0, 1]")

        self._do_sample = bool(do_sample)
        self._top_k = None if top_k is None else int(top_k)

        # host page table + lazy device mirror (refreshed only when an
        # allocation changes it — steady-state decode re-sends nothing)
        self._table = onp.zeros((self.max_slots, self.pages_per_slot),
                                onp.int32)
        self._table_dev = None
        self._table_dirty = True

        # the pools pytree (`serve/pages.py`): one leaf a layer and kind
        self._pools = None
        self._prefill_jit = None
        self._decode_jit = None
        # the tokens of the last decode launch, on the device: the next
        # launch reads a continuing slot's last token from them
        self._tokens = None

        # -- speculative decoding --------------------------------------
        sk_env = env_int("MXNET_SERVE_SPEC_K", 0)
        self.spec_k = int(spec_k) if spec_k is not None else sk_env
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if draft is None:
            draft = os.environ.get("MXNET_SERVE_SPEC_DRAFT", "ngram")
        self.draft_kind = "off"
        self._draft_dec = None
        self._ngram = None
        if self.spec_k:
            if self._do_sample:
                raise ValueError(
                    "speculative decoding (spec_k > 0) requires greedy "
                    "decoding (do_sample=False): greedy verification is "
                    "what makes spec output token-for-token identical")
            if isinstance(draft, str):
                if draft not in ("ngram",):
                    raise ValueError(
                        f"unknown draft source {draft!r} "
                        "(MXNET_SERVE_SPEC_DRAFT): expected 'ngram', a "
                        "GPTDecoder, or a GPT-shaped Block")
                self.draft_kind = "ngram"
                self._ngram = NgramProposer(self.spec_k)
            else:
                dd = draft if isinstance(draft, GPTDecoder) \
                    else GPTDecoder(draft)
                if dd._max_length < self.max_len:
                    raise ValueError(
                        f"draft model position table ({dd._max_length}) "
                        f"is shorter than max_len ({self.max_len})")
                dv = dd._params["embed"].shape[0]
                tv = self._dec._params["embed"].shape[0]
                if dv != tv:
                    raise ValueError(
                        f"draft vocab ({dv}) != target vocab ({tv}) — "
                        "drafted token ids index the target embedding")
                self.draft_kind = "model"
                self._draft_dec = dd
        self._draft_pools = None            # the draft model's own pools
        self._verify_jit = None
        self._draft_jit = None
        self._draft_prefill_jit = None
        self._spec_drafted = 0              # lifetime drafted tokens
        self._spec_accepted = 0             # lifetime accepted drafts
        self._spec_gauge = False

        # compile-ledger / HBM-census attribution label; the gateway
        # overrides this per model BEFORE the first prefill so ledger
        # families and census owners carry the tenant name
        self.census_name = "serve"

    def _resolve_decoder(self, source):
        """The decoder object this engine's programs are built for."""
        if isinstance(source, GPTDecoder):
            return source
        if hasattr(source, "blocks") and hasattr(source, "position_embed"):
            return GPTDecoder(source)
        raise TypeError(
            f"{type(self).__name__} needs a GPTDecoder or a GPT-shaped Block "
            f"(blocks + position_embed), got {type(source).__name__}")

    # -- page arithmetic (the scheduler asks; it keeps none of its own) -----

    def _table_width(self):
        """Entries of a slot's row of the page table."""
        return self.view_tokens // self.page_tokens

    def _quarter_and_whole_buckets(self):
        """Two prefill programs, a quarter chunk and a whole one, in the
        place of one a power of two: for a family whose prompts are many
        chunks long, of which only the last is padded."""
        return tuple(b for b in (self.prefill_chunk // 4, self.prefill_chunk)
                     if b and b % self.page_tokens == 0)

    def pages_needed(self, n):
        """The most pages a request holds at once while it writes K/V for
        positions ``0 .. n-1``: the admission budget."""
        return -(-int(n) // self.page_tokens)

    def pages_at(self, n):
        """Pages a slot holds with positions ``0 .. n-1`` mapped."""
        return -(-int(n) // self.page_tokens)

    def _row_of(self, pos):
        """Where position `pos` of a slot lives (traced): ``(entry of the
        slot's row of the page table, offset in that page, rows the position
        attends, its own among them)``."""
        pt = self.page_tokens
        return pos // pt, pos % pt, pos + 1

    def _count_rows(self, at):
        """Count what a decode step with slots at positions `at` (host
        array) attends; returns the pages it reads."""
        return int((at // self.page_tokens + 1).sum())

    # -- page table ---------------------------------------------------------

    def set_slot_pages(self, slot, pages):
        """Bind `pages` (host ints) as `slot`'s logical token range;
        entries past the list point at the trash page."""
        if len(pages) > self.pages_per_slot:
            raise ValueError(
                f"{len(pages)} pages exceed the slot view "
                f"({self.pages_per_slot})")
        self._table[slot, :] = 0
        self._table[slot, :len(pages)] = pages
        self._table_dirty = True

    def clear_slot(self, slot):
        self._table[slot, :] = 0
        self._table_dirty = True

    def _table_device(self):
        if self._table_dirty or self._table_dev is None:
            self._table_dev = _upload(self._table)
            self._table_dirty = False
        return self._table_dev

    # -- pool ---------------------------------------------------------------

    def _make_pools(self, dec):
        """`dec`'s pools pytree (`pages.make_pools`: what a stored page is,
        is decided there and known to the cache-access objects alone)."""
        return make_pools(self.n_pages, self.page_tokens, dec.kv_geometry(),
                          self.kv_dtype)

    # -- sharding seams (overridden by serve.sharded.ShardedSlotDecoder) ----

    def _refresh_params(self):
        """Hot-swap seam: re-read decoder params when the source block's
        weights changed (cheap id-fingerprint walk). The sharded engine
        overrides this to re-place refreshed params onto its mesh —
        every program entry point routes through here, so a weight swap
        lands without draining the engine."""
        self._dec._auto_refresh()

    def _mesh_scope(self):
        """The mesh the engine's programs are traced under, made visible
        to the kernel sites' dispatch (`ops._dispatch.use_pallas`): none
        for the one-device engine."""
        return contextlib.nullcontext()

    def _place_pools(self, pools):
        """Placement seam for pools made outside a program (fresh zeros, a
        migration's eager scatters): the base engine keeps them as they
        are; the sharded engine pins them to the pool layout so donation
        aliasing matches."""
        return pools

    def _constrain_pools(self, pools):
        """Traced seam at the tail of every pool-updating program: the
        base engine is layout-free (identity), the sharded engine pins
        each updated pool leaf to its input sharding so XLA's donation
        map still aliases every leaf in place."""
        return pools

    def _pin_tokens(self, tokens):
        """Seam for a decode launch's ``(max_slots,)`` tokens, which the
        next launch takes back: traced at the program's tail, and applied
        to the zeros the first launch takes. Identity here; the sharded
        engine pins them replicated, so that the first launch and every
        later one see one input placement (no second compile)."""
        return tokens

    #: int32 values a decode or chunk program sends beside its tokens, in
    #: the same array and so in the same fetch (`_step_out`, `fetch_tokens`)
    step_extra = 0

    def _step_out(self, tokens, cache):  # noqa: ARG002
        """Traced seam at the tail of the chunk and decode programs: what
        goes back to the host beside the pools. The tokens as they are
        here; a family whose layers count something a step (`serve/mla.py`:
        the expert layer's held pairs) appends `step_extra` values."""
        return tokens

    def fetch_tokens(self, out):
        """A launched decode step's tokens on the host, ``(max_slots,)``:
        blocks until the step ran (the one host sync of a step)."""
        return onp.asarray(out)

    def fetch_first(self, out):
        """A launched final chunk's first token on the host (blocks)."""
        return int(out)

    def _shardcheck_specs(self):
        """``(spec entries for (params, pools), spec entries for the
        builders' (pools, tok) outputs)`` for the shardcheck pre-flight, or
        ``(None, None)`` (unconstrained — the single-chip default). The
        sharded engine returns its `ServeLayout`-derived entries so SC001
        sees every ≥1 MiB leaf explicitly placed and the SC004 donation
        audit sees matching in/out placements."""
        return None, None

    def _ensure_pool(self):
        if self._pools is not None:
            return
        self._pools = self._place_pools(self._make_pools(self._dec))
        if self._draft_dec is not None:
            self._draft_pools = self._place_pools(
                self._make_pools(self._draft_dec))
        self._register_hbm_owners()

    def _pool_leaves(self):
        """Every device array of the pools, target and draft."""
        return _j().tree.leaves((self._pools, self._draft_pools))

    def device_dry(self):
        """Whether everything this engine launched has finished, without
        blocking (one ``is_ready()``). Every program takes the pools donated
        and returns them first (`_observed`), so any leaf of ``_pools`` is an
        output of the newest program launched, whatever its kind; on the
        device's one in-order stream that leaf is ready when nothing queued
        is left. (The draft program writes ``_draft_pools``, and its round
        fetches what it launches.) True before the first launch."""
        pools = self._pools
        return pools is None or next(iter(pools.values()))[0].is_ready()

    def _register_hbm_owners(self):
        """Attribute this engine's device memory to named HBM-census
        owners (`telemetry.hbm`): the KV pool (+ page table, with the
        prefix cache's share as derived page math — cached pages live
        inside the pool arrays) and the decoder params. Probes hold a
        weakref so a released engine silently drops out of the census."""
        ref = weakref.ref(self)

        def _pool_probe():
            eng = ref()
            if eng is None or eng._pools is None:
                return None
            arrays = eng._pool_leaves() + [eng._table_dev]
            page_bytes = eng.cache_bytes / eng.n_pages if eng.n_pages else 0
            cached = eng.prefix_cache.cached_pages
            return {
                "arrays": [a for a in arrays if a is not None],
                "detail": {"kv_dtype": eng.kv_dtype,
                           "n_pages": eng.n_pages,
                           "pages_used": eng.allocator.used_pages,
                           "prefix_cached_pages": cached,
                           **eng._pool_detail()},
                "derived": {"prefix_cache": int(cached * page_bytes)},
            }

        def _params_probe():
            eng = ref()
            if eng is None:
                return None
            return {"arrays": _j().tree.leaves(eng._dec._params)}

        _hbm.register_owner(f"{self.census_name}.kv_pool", _pool_probe)
        _hbm.register_owner(f"{self.census_name}.params", _params_probe)

    def _pool_detail(self):
        """What a family adds to the KV-pool owner's census detail (the
        state a slot keeps beside its pages: `serve/ssm.py`)."""
        return {}

    def release(self):
        """Drop the device pool (shutdown); the next prefill reallocates."""
        self._pools = self._draft_pools = None
        self._tokens = None
        self._table_dev = None
        self._table_dirty = True

    @property
    def cache_bytes(self):
        """Device bytes held by the persistent KV pools — target and
        (when a model draft is armed) draft — 0 if released."""
        return sum(a.size * a.dtype.itemsize for a in self._pool_leaves())

    @property
    def kv_bytes_per_slot(self):
        """Resident pool bytes per decode slot — the HBM cost a slot
        actually pays under paging (int8 halves it)."""
        return self.cache_bytes / self.max_slots

    @property
    def page_bytes(self):
        """Bytes one pool page holds across all L layers (K + V, plus
        the int8 scale planes) — the migration accounting unit: the
        disaggregation plane's ``mx_serve_page_migration_bytes_total``
        is exactly pages-moved × this. Derived from shapes, so it needs
        no allocated pool."""
        return page_bytes(self.page_tokens, self._dec.kv_geometry(),
                          self.kv_dtype)

    # -- page migration (the disaggregation transfer seam) -------------------

    def copy_pages_out(self, pages):
        """Snapshot pool pages `pages` to host — the export half of the
        disagg KV handoff (`serve/disagg.py` is the only caller; lint
        FL021 fences everything else off). Returns an opaque payload for
        a same-shape peer's `copy_pages_in`.

        Pages are gathered ONE at a time with the page index as a traced
        device scalar: every dispatch reuses a single cached executable
        per layer shape regardless of how many pages a request spans, so
        steady-state migration compiles nothing new (the instrumented
        prefill/decode families are untouched either way)."""
        jnp = _j().numpy
        self._ensure_pool()
        return {name: [[onp.asarray(jnp.take(leaf, jnp.asarray(p, jnp.int32),
                                             axis=0))
                        for p in pages]
                       for leaf in leaves]
                for name, leaves in self._pools.items()}

    def copy_pages_in(self, pages, payload):
        """Write a peer engine's `copy_pages_out` payload into this pool
        at `pages` (import half of the disagg handoff; same whole-page
        granularity, so the bytes land bit-identical). Like the export
        side, one traced-index scatter per page keeps every executable
        shape-stable across migrations."""
        jnp = _j().numpy
        self._ensure_pool()
        if set(payload) != set(self._pools):
            raise ValueError(
                f"payload carries {sorted(payload)} planes but this engine "
                f"holds {sorted(self._pools)} (kv_dtype mismatch across "
                "replicas?)")

        def write(leaf, per_page):
            for p, blk in zip(pages, per_page):
                leaf = leaf.at[jnp.asarray(p, jnp.int32)].set(
                    jnp.asarray(blk))
            return leaf

        self._pools = self._place_pools({
            name: tuple(map(write, leaves, payload[name]))
            for name, leaves in self._pools.items()})

    # -- the programs' skeleton ---------------------------------------------

    def _run_layers(self, dec, params, tokens, pos, cache):
        """`dec`'s block over ``tokens`` (N, T) — N sequences, T new rows
        each; or (N,), one new row each — at positions `pos`, every layer
        handed `cache`; returns the last layer's residual rows ``(N T, C)``.

        Python-unrolled over layers: each iteration reads/writes ITS OWN
        donated pool leaf, so XLA's donation map aliases every leaf in place
        (a scan over a stacked pool re-stacks the whole pool per call — the
        O(L x n_pages) rewrite the per-layer pools exist to remove), and
        takes ITS OWN parameter leaves as they are stored (a slice of stacked
        weights is a copy the chip re-lays out every step: `PERF.md` §6,
        PR 32)."""
        x = dec.embed(params, tokens, pos)
        for li in range(len(dec.layer_kinds())):
            x = dec.layer(li, dec.layer_params(params, li), x, pos, cache)
        return x.reshape(-1, x.shape[-1])

    def _observed(self, fn, kind, tokens_idx=None, **jit_kwargs):
        """The one way a program of this engine is jitted: its pools,
        argument 1, donated, and the program in the compile ledger as
        family ``<census_name>.<kind>`` — recompiles past the first get
        forensics, and bucketed prefill growth (a new chunk bucket seen at
        `tokens_idx`) is classified `new_bucket`. The wrapper passes
        `_cache_size` through, so `xla_program_count` and the shardcheck
        pre-flight see the raw jitted object's introspection surface."""
        bucket = None
        if tokens_idx is not None:
            def bucket(args, kwargs, _i=tokens_idx):  # noqa: ARG001
                return int(args[_i].shape[1])
        return _compiles.ledgered_jit(
            fn, family=f"{self.census_name}.{kind}", bucket=bucket,
            donate_argnums=(1,), **jit_kwargs)

    # -- chunked prefill ----------------------------------------------------

    def _build_prefill(self, dec=None, kind="prefill"):
        """Chunked-prefill program family for `dec` (default the target;
        the draft model gets its own family writing its own pools)."""
        jax = _j()
        jnp = jax.numpy
        dec = self._dec if dec is None else dec

        def prefill(params, pools, tokens, pages, t_start, t_len, key,
                    temperature, *, top_k, do_sample):
            n = tokens.shape[1]
            cache = self._chunk_cache(pools, pages, t_start, t_len)
            x = self._run_layers(dec, params, tokens,
                                 (t_start + jnp.arange(n))[None, :], cache)
            # the chunk's last REAL row (padding beyond t_len is causally
            # downstream of it and cannot touch it)
            last = jax.lax.dynamic_slice_in_dim(x, t_len - 1, 1, axis=0)
            first = self._sample_slots(
                dec.next_logits(params, last), key, temperature[None],
                top_k, do_sample)                              # (1,)
            return cache.pools(), self._step_out(first[0], cache)

        return self._observed(prefill, kind, tokens_idx=2,
                              static_argnames=("top_k", "do_sample"))

    def _chunk_pages(self, slot, t_start, bucket):
        """Host half of a chunk's cache access: where the chunk of `bucket`
        tokens at `t_start` of `slot` is written and what it attends, as the
        device arrays `_chunk_cache` takes (the launch's own copies)."""
        jnp = _j().numpy
        pt = self.page_tokens
        if t_start % pt:
            raise ValueError(
                f"chunk start {t_start} is not page-aligned "
                f"(page_tokens={pt})")
        # the chunk's pages, padded with the trash page where the
        # bucket overshoots the slot's mapped range (pad-token K/V is
        # discarded)
        row = self._table[slot].copy()
        first_page = t_start // pt
        chunk_pages = onp.zeros(bucket // pt, onp.int32)
        avail = row[first_page:first_page + bucket // pt]
        chunk_pages[:avail.size] = avail
        return jnp.asarray(row), jnp.asarray(chunk_pages)

    def _chunk_cache(self, pools, pages, t_start, t_len):  # noqa: ARG002
        """Traced half: the cache-access object of a chunk of `t_len` real
        rows at `t_start` whose `pages` are `_chunk_pages`'s."""
        return ChunkCache(self, pools, *pages, t_start)

    def _to_bucket(self, chunk_tokens):
        """``(tokens padded to their bucket, real length, bucket, pad)`` of
        a prefill chunk (the waste is counted)."""
        chunk = onp.asarray(chunk_tokens, onp.int32).reshape(-1)
        n = chunk.size
        bucket = bucket_chunk(n, self.chunk_buckets)
        pad = bucket - n
        if pad:
            chunk = onp.pad(chunk, (0, pad))
            PAD_TOKENS.inc(pad)
        return chunk, n, bucket, pad

    def prefill_chunk_step(self, slot, chunk_tokens, t_start, key,
                           temperature=1.0):
        """Run ONE prefill chunk for `slot`.

        `chunk_tokens` is the 1D host slice ``prompt[t_start:t_start+n]``
        with ``t_start`` aligned as the family's pages ask (`_chunk_pages`:
        to a page here, e.g. the shared-prefix boundary). The chunk is
        LAUNCHED, not waited for. Returns ``(first_token, bucket, pad)`` —
        the sampled token as the program gives it, a device scalar not yet
        fetched: it is meaningful only when this was the prompt's final
        chunk, and only then does the caller fetch it (``int(first)``,
        which blocks until the chunk ran); `bucket`/`pad` feed the caller's
        span annotations. `key` is a PRNG key or a callable that makes one: the
        scheduler hands its key maker in, so that the eager ``fold_in``
        runs inside the launch span with the rest of the host's work.
        """
        jnp = _j().numpy
        with tracing.phase("mx.serve.prefill.launch",
                           "prefill_launch") as launch:
            self._refresh_params()
            self._ensure_pool()
            if self._prefill_jit is None:
                self._prefill_jit = self._build_prefill()
            chunk, n, bucket, pad = self._to_bucket(chunk_tokens)
            pages = self._chunk_pages(slot, t_start, bucket)
            if callable(key):
                key = key()
            args = (jnp.asarray(chunk)[None, :], pages, jnp.int32(t_start),
                    jnp.int32(n), key,
                    jnp.float32(max(float(temperature), 1e-6)))
            launch.site()
            self._pools, first = self._prefill_jit(
                self._dec._params, self._pools, *args, top_k=self._top_k,
                do_sample=self._do_sample)
            if self._draft_dec is not None:
                # the draft model prefills the SAME chunk into its own
                # pools (same pages — table/allocator are shared), so
                # spec drafting starts from a warm draft KV for every
                # request
                self._draft_dec._auto_refresh()
                if self._draft_prefill_jit is None:
                    self._draft_prefill_jit = self._build_prefill(
                        self._draft_dec, "draft_prefill")
                self._draft_pools, _ = self._draft_prefill_jit(
                    self._draft_dec._params, self._draft_pools, *args,
                    top_k=self._top_k, do_sample=self._do_sample)
        return first, bucket, pad

    # -- decode -------------------------------------------------------------

    def _sample_slots(self, logits, key, temperature, top_k, do_sample):
        """`GPTDecoder._sample` with a PER-SLOT temperature vector."""
        jax = _j()
        jnp = jax.numpy
        if not do_sample:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits.astype(jnp.float32) / temperature[:, None]
        if top_k is not None:
            vals, idx = jax.lax.top_k(logits, top_k)
            choice = jax.random.categorical(key, vals, axis=-1)
            return jnp.take_along_axis(
                idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    def _build_decode(self):
        jnp = _j().numpy
        dec = self._dec

        def decode(params, pools, table, last_tok, prev_tok, pos, active,
                   key, temperature, *, top_k, do_sample):
            # a slot that goes on from the launch before takes the token
            # that launch gave it, which the host may not have seen yet
            last_tok = jnp.where(last_tok < 0,
                                 prev_tok[:last_tok.shape[0]], last_tok)
            cache = self._token_cache(pools, table, pos, active)
            x = self._run_layers(dec, params, last_tok, pos, cache)
            nxt = self._sample_slots(dec.next_logits(params, x), key,
                                     temperature, top_k, do_sample)
            # free/prefilling slots carry their last token forward — the
            # host never reads them, but a defined value keeps the
            # program deterministic
            nxt = self._pin_tokens(self._step_out(
                jnp.where(active, nxt, last_tok), cache))
            return cache.pools(), nxt

        return self._observed(decode, "decode",
                              static_argnames=("top_k", "do_sample"))

    def _token_cache(self, pools, table, pos, active):
        """Traced: the cache-access object of a decode step whose slots
        stand at `pos`."""
        return TokenCache(self, pools, table, *self._row_of(pos), active)

    def decode_step(self, last_tok, pos, active, key, temperature):
        """LAUNCH one decode step for every DECODE-ACTIVE slot. `last_tok`
        / `pos` / `active` / `temperature` are HOST arrays (shape
        ``(max_slots,)``) owned by the scheduler — the step loop never
        branches on device values. Slots still mid-prefill must have
        ``active=False`` (their writes are redirected to the trash page).
        A NEGATIVE entry of `last_tok` stands for "the token the launch
        before this one produced for that slot": it is taken from that
        launch's output on the device, so the caller can queue this step
        before it has fetched the last one's tokens. Returns the next token
        per slot as the program gives it, a device array NOT yet fetched:
        `fetch_tokens` of it is the one host sync of a step, and whoever
        needs the tokens makes it (`Scheduler._land`). `key`: a PRNG key, or
        a callable that makes one (called inside the launch span, as in
        `prefill_chunk_step`). The launch is stamped in four parts
        (`tracing.launch_phase`), each a span nested in
        ``mx.serve.decode.launch`` and a step-record field
        (`tracing.step_records`): prepare, key, upload, dispatch; the
        dispatch is the launch site the dry account watches."""
        jnp = _j().numpy
        with tracing.launch_phase() as boundary:
            # prepare (with the scheduler's work since the boundary before)
            self._refresh_params()
            self._ensure_pool()
            if self._decode_jit is None:
                self._decode_jit = self._build_decode()
            if self._tokens is None:
                self._tokens = self._pin_tokens(
                    jnp.zeros(self.max_slots + self.step_extra, jnp.int32))
            table = self._table_device()
            boundary()                    # key: the eager fold_in
            if callable(key):
                key = key()
            boundary()
            # upload: the launch's own copies of the host's arrays, around
            # the tokens of the launch before
            args = (_upload(last_tok, onp.int32), self._tokens,
                    _upload(pos, onp.int32), _upload(active, bool), key,
                    _upload(temperature, onp.float32))
            boundary()                    # dispatch: the launch site
            self._pools, self._tokens = self._decode_jit(
                self._dec._params, self._pools, table, *args,
                top_k=self._top_k, do_sample=self._do_sample)
            boundary()
            # the counters: the launch's, in none of its four parts
            live = self._count_rows(
                onp.asarray(pos, onp.int64)[onp.asarray(active, bool)])
            view = self.max_slots * self.pages_per_slot
            DECODE_PAGES["live"].inc(live)
            DECODE_PAGES["view"].inc(view)
            tracing.count(pages_live=live, pages_view=view)
        return self._tokens

    # -- speculative decoding ----------------------------------------------

    def _build_verify(self):
        """ONE batched target program: consume ``[last, d_1..d_k]`` per
        slot (k+1 rows at positions ``pos..pos+k``), write each row's
        K/V to the slot's pages, and emit the greedy next token per row.
        Row ``i`` attends only positions ``<= pos + i``, so the batch is
        mathematically identical to k+1 sequential decode steps — the
        identity that makes greedy spec decode bit-equal to plain
        decode. Rows past a slot's mapped pages (``p > limit``) are
        redirected to the trash page; the scheduler never commits their
        outputs."""
        jnp = _j().numpy
        dec = self._dec
        pt = self.page_tokens
        S = self.max_slots
        K1 = self.spec_k + 1

        def verify(params, pools, table, toks, pos, active, limit):
            p_abs = pos[:, None] + jnp.arange(K1)[None, :]     # (S, K1)
            writable = active[:, None] & (p_abs <= limit[:, None])
            wpage = jnp.take_along_axis(
                table, jnp.clip(p_abs // pt, 0, table.shape[1] - 1), axis=1)
            cache = RowsCache(self, pools, table,
                              jnp.where(writable, wpage, 0), p_abs % pt,
                              p_abs)
            x = self._run_layers(dec, params, toks, p_abs, cache)
            logits = dec.next_logits(params, x).reshape(S, K1, -1)
            tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return cache.pools(), jnp.where(active[:, None], tgt, toks)

        return self._observed(verify, "verify")

    def _build_draft(self):
        """ONE draft-model program: k unrolled greedy decode steps
        (each step identical in structure to the decode program, against
        the draft's own per-layer pools) — k drafted tokens per launch,
        feeding the target's verify program."""
        jnp = _j().numpy
        dec = self._draft_dec

        def draft(params, pools, table, last_tok, pos, active, limit):
            cur, leaves = last_tok, pools
            outs = []
            for i in range(self.spec_k):
                p_i = pos + i
                col, woff, rows = self._row_of(p_i)
                cache = TokenCache(
                    self, leaves, table,
                    jnp.clip(col, 0, table.shape[1] - 1), woff, rows,
                    active, active & (p_i <= limit))
                x = self._run_layers(dec, params, cur, p_i, cache)
                nxt = jnp.argmax(dec.next_logits(params, x),
                                 axis=-1).astype(jnp.int32)
                cur = jnp.where(active, nxt, cur)
                outs.append(cur)
                leaves = cache.leaves
            return cache.pools(), jnp.stack(outs, axis=1)       # (S, K)

        return self._observed(draft, "draft")

    def spec_propose(self, seqs):
        """Host n-gram drafts: `seqs` is a per-slot list (None for
        slots not decoding) of 1-D prompt+generated token arrays.
        Returns ``(max_slots, spec_k)`` int32 host numpy. No device
        program — the ngram draft's entire cost is this call."""
        out = onp.zeros((self.max_slots, self.spec_k), onp.int32)
        with tracing.phase("mx.serve.spec.draft.propose", "decode_launch"):
            for s, seq in enumerate(seqs):
                if seq is not None:
                    out[s] = self._ngram.propose(seq)
        return out

    def _spec_args(self, toks, pos, active, limit):
        """What the draft and verify programs take after the pools."""
        jnp = _j().numpy
        return (self._table_device(), jnp.asarray(toks, jnp.int32),
                jnp.asarray(pos, jnp.int32), jnp.asarray(active, bool),
                jnp.asarray(limit, jnp.int32))

    def spec_draft_step(self, last_tok, pos, active, limit):
        """Run the draft model's k-step program; returns drafted tokens
        ``(max_slots, spec_k)`` as host numpy."""
        with tracing.phase("mx.serve.spec.draft.launch",
                           "decode_launch") as launch:
            self._draft_dec._auto_refresh()
            self._ensure_pool()
            if self._draft_jit is None:
                self._draft_jit = self._build_draft()
            args = self._spec_args(last_tok, pos, active, limit)
            launch.site()
            self._draft_pools, toks = self._draft_jit(
                self._draft_dec._params, self._draft_pools, *args)
        with tracing.phase("mx.serve.spec.draft.readback",
                           "decode_readback"):
            return onp.asarray(toks)

    def spec_verify_step(self, last_tok, drafts, pos, active, limit):
        """Verify ``drafts`` (host ``(max_slots, spec_k)``) for every
        decoding slot in ONE batched target program. Returns the greedy
        target token per row as host numpy ``(max_slots, spec_k + 1)``:
        row ``i`` is the token the target emits after consuming
        ``[last, d_1..d_i]`` — the scheduler accepts the longest drafted
        prefix matching rows ``0..m-1`` plus row ``m`` as the bonus
        token (>= 1 token of guaranteed progress per round)."""
        with tracing.phase("mx.serve.spec.verify.launch",
                           "decode_launch") as launch:
            self._refresh_params()
            self._ensure_pool()
            if self._verify_jit is None:
                self._verify_jit = self._build_verify()
            if not self._spec_gauge:
                self._register_spec_gauge()
            toks = onp.concatenate(
                [onp.asarray(last_tok, onp.int32)[:, None],
                 onp.asarray(drafts, onp.int32)], axis=1)
            args = self._spec_args(toks, pos, active, limit)
            launch.site()
            self._pools, tgt = self._verify_jit(
                self._dec._params, self._pools, *args)
        with tracing.phase("mx.serve.spec.verify.readback",
                           "decode_readback"):
            return onp.asarray(tgt)

    def spec_count(self, drafted, accepted):
        """Scheduler callback: fold one slot-round's drafted/accepted
        token counts into the engine's lifetime acceptance stats."""
        self._spec_drafted += int(drafted)
        self._spec_accepted += int(accepted)

    def spec_stats(self):
        """Lifetime speculative-decoding stats for this engine —
        surfaced per model in the gateway flight-recorder context."""
        drafted = self._spec_drafted
        return {"k": self.spec_k, "draft": self.draft_kind,
                "drafted": drafted, "accepted": self._spec_accepted,
                "accept_rate": (self._spec_accepted / drafted)
                if drafted else None}

    def _register_spec_gauge(self):
        """Per-model pull gauge for the lifetime acceptance rate;
        registered on first verify so the gateway's census_name
        override has already landed. Weakref probe, like the HBM
        owners."""
        self._spec_gauge = True
        ref = weakref.ref(self)

        def probe():
            eng = ref()
            if eng is None or not eng._spec_drafted:
                return None
            return eng._spec_accepted / eng._spec_drafted

        registry.register_pull_gauge(
            "mx_serve_spec_accept_rate", probe,
            "accepted draft tokens / drafted tokens since engine start "
            "[0, 1] (speculative decoding)",
            labels={"model": self.census_name})

    # -- debug / tests ------------------------------------------------------

    def slot_kv(self, slot, n_tokens):
        """Host copy of a slot's first `n_tokens` of K and V (dequantized
        under int8) — parity/tolerance checks in tests, not a hot path."""
        jnp = _j().numpy
        self._ensure_pool()
        cache = PageCache(self, self._pools)
        idx = jnp.asarray(self._table[slot])
        rows = [cache.rows(li, idx) for li in range(len(self._pools["k"]))]
        return tuple(
            onp.asarray(jnp.stack([r[kv][:, :n_tokens] for r in rows]),
                        onp.float32) for kv in (0, 1))

    def xla_program_count(self):
        """Number of compiled programs across every family this engine
        owns: chunk-prefill (one per chunk bucket actually seen), decode,
        and — with spec decode armed — verify, draft, and draft-prefill.
        The recompile-count gate of `tests/test_serve.py` asserts this
        stays constant in steady state."""
        n = 0
        for f in (self._prefill_jit, self._decode_jit, self._verify_jit,
                  self._draft_jit, self._draft_prefill_jit):
            if f is None:
                continue
            size = getattr(f, "_cache_size", None)
            if size is not None:
                n += int(size())
        return n

    def shardcheck_report(self, mesh=None, hbm_budget_gb=None,
                          bucket=None):
        """Static sharding pre-flight (`mx.analysis.shardcheck`) over the
        engine's two compiled program families: the chunked-prefill jit
        (analyzed at `bucket`, default the largest chunk bucket) and the
        decode jit, which is audited as a latency hot path.

        With the default ``mesh=None`` (one device) this is a per-device
        byte budget (SC006) plus the donation audit (SC004); the sharded
        engine passes its mesh and layout (`_shardcheck_specs`). Returns
        ``{"prefill": ShardReport, "decode": ShardReport}``.
        """
        import functools

        from ..analysis.shardcheck import shardcheck
        from ..random import next_key

        jax = _j()
        sds = jax.ShapeDtypeStruct
        self._refresh_params()
        self._ensure_pool()
        if self._prefill_jit is None:
            self._prefill_jit = self._build_prefill()
        if self._decode_jit is None:
            self._decode_jit = self._build_decode()
        S = self.max_slots
        key = next_key()
        i32, f32 = jax.numpy.int32, jax.numpy.float32
        bucket = int(bucket) if bucket is not None else self.chunk_buckets[-1]
        head_specs, out_specs = self._shardcheck_specs()

        def check(jitted, args, name, **kw):
            args = (self._dec._params, self._pools) + args
            specs = None if head_specs is None else head_specs + (
                (None,) * (len(args) - len(head_specs)))
            return shardcheck(
                functools.partial(jitted, top_k=self._top_k,
                                  do_sample=self._do_sample),
                *args, mesh=mesh, specs=specs, out_specs=out_specs,
                donate_argnums=(1,), hbm_budget_gb=hbm_budget_gb, name=name,
                **kw)

        prefill = check(self._prefill_jit, (
            sds((1, bucket), i32),                      # tokens
            jax.eval_shape(lambda: self._chunk_pages(0, 0, bucket)),
            sds((), i32), sds((), i32),                 # t_start, t_len
            key, sds((), f32)),                         # key, temperature
            f"SlotDecoder.prefill[b{bucket}]")
        decode = check(self._decode_jit, (
            sds((S, self.pages_per_slot), i32),         # page table
            sds((S,), i32), sds((S,), i32),             # last_tok, prev_tok
            sds((S,), i32), sds((S,), bool),            # pos, active
            key, sds((S,), f32)),                       # key, temperature
            "SlotDecoder.decode", hot_path=True)
        return {"prefill": prefill, "decode": decode}

    def hbm_crosscheck(self, mesh=None):
        """Runtime-vs-static HBM accounting: compare the live-buffer
        census bytes attributed to THIS engine (KV pool + params owners)
        against shardcheck's SC006 per-device estimate for the decode
        program. The two are independent derivations — census sweeps
        ``jax.live_arrays()``, SC006 sums abstract avals — so agreement
        (the acceptance gate asks within 15%) validates both. Returns
        ``{"census_bytes", "sc006_bytes", "ratio", "owners"}``."""
        report = self.shardcheck_report(mesh=mesh)
        sc006 = int(report["decode"].per_device_bytes)
        c = _hbm.census(top_k=0)
        mine = {k: v for k, v in c["owners"].items()
                if k.startswith(f"{self.census_name}.")}
        total = sum(mine.values())
        return {"census_bytes": total, "sc006_bytes": sc006,
                "ratio": (total / sc006) if sc006 else None,
                "owners": mine}


def _occupancy_probe(allocator):
    """Weakly-bound pull probe for the page-occupancy gauge (engines come
    and go in tests; a dead allocator must not pin memory or poison the
    collector)."""
    ref = weakref.ref(allocator)

    def probe():
        a = ref()
        if a is None or a.usable_pages == 0:
            return None
        return a.used_pages / a.usable_pages

    return probe
