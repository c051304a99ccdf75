"""Paged slot-cache compiled decode programs (the device half of `mx.serve`).

PR 4's engine kept one monolithic KV slot per request — shape
``(L, max_slots, H, max_len, d)`` — so every slot reserved ``max_len``
HBM regardless of actual request length and every prompt paid a full
prefill. This module replaces it with a **paged** pool, the
vLLM/PagedAttention block-allocation idea re-expressed TPU-natively
(static shapes, gather-by-page-table, zero steady-state recompiles):

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
  it to the pages under each slot's ``pos`` and to no others; prefill
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

Two compiled program families in the base configuration:

- **chunked prefill** (one program per chunk-length bucket,
  `models.decoding.chunk_buckets`): one page-aligned chunk of ONE
  request's prompt — embeds the chunk at its true positions (traced
  ``t_start``), writes the chunk's K/V pages into the pool, attends the
  chunk's queries against the slot's gathered view (prefix pages +
  itself) under a causal-with-offset mask, and samples a first token
  from the chunk's last real row (used by the host only on the final
  chunk). Splitting long prompts into chunks lets the scheduler
  interleave decode steps between chunks, so a long-prompt arrival no
  longer stalls every running request for a whole monolithic prefill.
- **decode** (ONE program): one token for ALL slots — per-slot write
  of the new K/V at ``page_table[s, pos//page_tokens]`` (inactive slots
  are redirected to the trash page), attention over each decoding
  slot's live pages, per-slot sampling. The attention is one op with two
  implementations, chosen from what the process observes and counted in
  ``mx_kernel_dispatch_total{op="paged_decode_attention",impl=}``: on one
  TPU device with float pools the pallas kernel ``mx_paged_decode``,
  which reads the pages below ``pos`` straight from the layer's pool
  leaf and never builds the ``(S, H, max_len, d)`` view; on the CPU,
  under a multi-device mesh and for int8 pools the XLA expression —
  gather every slot's whole view through the table, mask, softmax, two
  einsums.
  ``mx_serve_decode_pages_total{kind="live"|"view"}`` says what share of
  that view a step's attention covers.

With **speculative decoding** armed (``spec_k > 0``), decode is
replaced by two more families that advance up to ``k + 1`` tokens per
round instead of one per launch:

- **verify** (ONE program): the target model runs ``k + 1`` token rows
  for ALL slots in one batched pass — row ``i`` consumes
  ``[last, d_1..d_k][i]`` at position ``pos + i``, writes its K/V to
  the slot's pages (beyond-budget rows are redirected to the trash
  page) and emits the greedy next token. Because row ``i`` only
  attends positions ``<= pos + i``, the batched pass is mathematically
  identical to ``k + 1`` sequential decode steps — the same identity
  chunked prefill already relies on — which is what makes greedy spec
  decode token-for-token equal to the non-spec engine.
- **draft** (ONE program, model drafts only): ``k`` unrolled greedy
  decode steps of the small draft model against its OWN per-layer pool
  (same page table and allocator, so draft pages track target pages
  exactly). The ``draft="ngram"`` fallback drafts on the host
  (`models.decoding.NgramProposer`) and adds NO device program.

Acceptance runs on host numpy in the scheduler: the longest drafted
prefix matching the verify row outputs commits (plus the bonus token
from the first mismatching row), and pages speculatively extended for
rejected suffixes roll back through `PageAllocator.decref`.

All families donate the pool buffers (``donate_argnums``) so XLA
updates them in place. Optional **int8 KV**
(``MXNET_SERVE_KV_DTYPE=int8``) stores each layer's pool as int8 with
one scale per (page, head) — the symmetric ±127 convention of
`contrib.quantization` (`quantize_symmetric`) — halving resident KV
bytes per slot; decode re-quantizes only the single page it writes
(grow-only per-page scale).

Stale-row safety (unchanged argument, now per page): position ``p`` of a
slot only enters the attention mask once the slot's ``pos`` reaches
``p``, and the program that advances ``pos`` to ``p`` writes ``p``'s K/V
first — so a freed-and-reused page's previous contents, chunk padding,
and generation headroom are all dead by construction.
"""
from __future__ import annotations

import contextlib
import hashlib
import math
import os
import weakref

import numpy as onp

from ..models.decoding import (GPTDecoder, NgramProposer, bucket_chunk,
                               chunk_buckets)
from ..telemetry import compiles as _compiles
from ..telemetry import hbm as _hbm
from ..telemetry import registry, tracing

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
    """Paged slot-cache decoder over a `GPTDecoder` (or the
    `GPTModel`-shaped Block it wraps).

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
        self._int8 = kv_dtype == "int8"

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

        # per-layer paged K/V: tuples of L arrays (n_pages, H, pt, d)
        self._pk = self._pv = None
        self._sk = self._sv = None          # int8 per-(page, H) scales
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
        self._dpk = self._dpv = None        # draft-model per-layer pools
        self._dsk = self._dsv = None
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
        if getattr(source, "family", None) == "evabyte":
            raise NotImplementedError(
                f"{type(self).__name__} does not serve the evabyte family "
                "(window and summary pages, the roll): `serve.eva."
                "EvaSlotDecoder` does, on one device; a sharded engine for "
                "it is not written")
        if isinstance(source, GPTDecoder):
            return source
        if hasattr(source, "blocks") and hasattr(source, "position_embed"):
            return GPTDecoder(source)
        raise TypeError(
            "SlotDecoder needs a GPTDecoder or a GPT-shaped Block "
            f"(blocks + position_embed), got {type(source).__name__}")

    def _kv_geometry(self, dec):
        """``(layers, heads, head size, float dtype)`` of `dec`'s K/V rows."""
        if hasattr(dec, "kv_geometry"):
            return dec.kv_geometry()
        layers = dec._params["layers"]
        return (int(layers["ln1_g"].shape[0]), dec._n_heads,
                dec._units // dec._n_heads, layers["qkv_w"].dtype)

    # -- page arithmetic (the scheduler asks; it keeps none of its own) -----

    def _table_width(self):
        """Entries of a slot's row of the page table."""
        return self.view_tokens // self.page_tokens

    def pages_needed(self, n):
        """The most pages a request holds at once while it writes K/V for
        positions ``0 .. n-1``: the admission budget."""
        return -(-int(n) // self.page_tokens)

    def pages_at(self, n):
        """Pages a slot holds with positions ``0 .. n-1`` mapped."""
        return -(-int(n) // self.page_tokens)

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
        """Per-layer page pools for `dec`: TUPLES of L device arrays of
        ``(n_pages, H, page_tokens, d)`` values, a float leaf packed to
        128 lanes (int8 adds per-layer ``(n_pages, H)`` scale planes).
        Separate leaves — not one
        stacked 5-D array — so every compiled program's donation map
        aliases each layer's pool in place; see the module docstring
        for why the stacked layout forces an O(L × n_pages) rewrite."""
        jnp = _j().numpy
        L, H, d, dtype = self._kv_geometry(dec)
        shape = (self.n_pages, H, self.page_tokens, d)
        if self._int8:
            pk = tuple(jnp.zeros(shape, jnp.int8) for _ in range(L))
            pv = tuple(jnp.zeros(shape, jnp.int8) for _ in range(L))
            sk = tuple(jnp.zeros((self.n_pages, H), jnp.float32)
                       for _ in range(L))
            sv = tuple(jnp.zeros((self.n_pages, H), jnp.float32)
                       for _ in range(L))
            return pk, pv, sk, sv
        # float pages are stored packed to the TPU's 128 lanes
        # (ops.paged_attention, "a page as it is stored")
        from ..ops.paged_attention import page_store_shape

        shape = (self.n_pages, H) + page_store_shape(self.page_tokens, d)
        pk = tuple(jnp.zeros(shape, dtype) for _ in range(L))
        pv = tuple(jnp.zeros(shape, dtype) for _ in range(L))
        return pk, pv, None, None

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

    def _constrain_pools(self, pk, pv, sk, sv):
        """Traced seam at the tail of every pool-updating program: the
        base engine is layout-free (identity), the sharded engine pins
        each updated pool leaf to its input sharding so XLA's donation
        map still aliases all ``2L`` leaves in place."""
        return pk, pv, sk, sv

    def _pin_tokens(self, tokens):
        """Seam for a decode launch's ``(max_slots,)`` tokens, which the
        next launch takes back: traced at the program's tail, and applied
        to the zeros the first launch takes. Identity here; the sharded
        engine pins them replicated, so that the first launch and every
        later one see one input placement (no second compile)."""
        return tokens

    def _shardcheck_specs(self):
        """Per-argument shardcheck spec entries for ``(params, *pools)``,
        or None (unconstrained — the single-chip default). The sharded
        engine returns its `ServeLayout`-derived entries so SC001 sees
        every ≥1 MiB leaf explicitly placed."""
        return None

    def _shardcheck_out_specs(self):
        """Spec entries for the builders' ``(pk, pv[, sk, sv], tok)``
        outputs, or None. The sharded engine pins the pool outputs so
        the SC004 donation audit sees matching in/out placements."""
        return None

    def _ensure_pool(self):
        if self._pk is not None:
            return
        self._pk, self._pv, self._sk, self._sv = self._make_pools(self._dec)
        if self._draft_dec is not None:
            (self._dpk, self._dpv,
             self._dsk, self._dsv) = self._make_pools(self._draft_dec)
        self._register_hbm_owners()

    def _register_hbm_owners(self):
        """Attribute this engine's device memory to named HBM-census
        owners (`telemetry.hbm`): the KV pool (+ page table, with the
        prefix cache's share as derived page math — cached pages live
        inside the pool arrays) and the decoder params. Probes hold a
        weakref so a released engine silently drops out of the census."""
        ref = weakref.ref(self)

        def _pool_probe():
            eng = ref()
            if eng is None or eng._pk is None:
                return None
            arrays = []
            for leaves in (eng._pk, eng._pv, eng._sk, eng._sv,
                           eng._dpk, eng._dpv, eng._dsk, eng._dsv):
                if leaves is not None:
                    arrays.extend(leaves)
            arrays.append(eng._table_dev)
            page_bytes = eng.cache_bytes / eng.n_pages if eng.n_pages else 0
            cached = eng.prefix_cache.cached_pages
            return {
                "arrays": [a for a in arrays if a is not None],
                "detail": {"kv_dtype": eng.kv_dtype,
                           "n_pages": eng.n_pages,
                           "pages_used": eng.allocator.used_pages,
                           "prefix_cached_pages": cached},
                "derived": {"prefix_cache": int(cached * page_bytes)},
            }

        def _params_probe():
            eng = ref()
            if eng is None:
                return None
            import jax.tree_util as jtu

            return {"arrays": jtu.tree_leaves(eng._dec._params)}

        _hbm.register_owner(f"{self.census_name}.kv_pool", _pool_probe)
        _hbm.register_owner(f"{self.census_name}.params", _params_probe)

    def release(self):
        """Drop the device pool (shutdown); the next prefill reallocates."""
        self._pk = self._pv = self._sk = self._sv = None
        self._dpk = self._dpv = self._dsk = self._dsv = None
        self._tokens = None
        self._table_dev = None
        self._table_dirty = True

    @property
    def cache_bytes(self):
        """Device bytes held by the persistent KV pools — target and
        (when a model draft is armed) draft — 0 if released."""
        if self._pk is None:
            return 0
        n = 0
        for leaves in (self._pk, self._pv, self._sk, self._sv,
                       self._dpk, self._dpv, self._dsk, self._dsv):
            if leaves is not None:
                n += sum(a.size * a.dtype.itemsize for a in leaves)
        return n

    @property
    def kv_bytes_per_slot(self):
        """Resident pool bytes per decode slot — the HBM cost a slot
        actually pays under paging (int8 halves it)."""
        if self._pk is None:
            return 0
        return self.cache_bytes / self.max_slots

    @property
    def page_bytes(self):
        """Bytes one pool page holds across all L layers (K + V, plus
        the int8 scale planes) — the migration accounting unit: the
        disaggregation plane's ``mx_serve_page_migration_bytes_total``
        is exactly pages-moved × this. Derived from shapes, so it needs
        no allocated pool."""
        L, H, d, dtype = self._kv_geometry(self._dec)
        if self._int8:
            # int8 K + V page slabs plus two f32 per-(page, H) scales
            per_layer = 2 * H * self.page_tokens * d + 2 * H * 4
        else:
            per_layer = 2 * H * self.page_tokens * d * dtype.itemsize
        return L * per_layer

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
        payload = {}
        for name, leaves in (("k", self._pk), ("v", self._pv),
                             ("sk", self._sk), ("sv", self._sv)):
            if leaves is None:
                continue
            payload[name] = [
                [onp.asarray(jnp.take(pool_l, jnp.asarray(p, jnp.int32),
                                      axis=0))
                 for p in pages]
                for pool_l in leaves]
        return payload

    def copy_pages_in(self, pages, payload):
        """Write a peer engine's `copy_pages_out` payload into this pool
        at `pages` (import half of the disagg handoff; same whole-page
        granularity, so the bytes land bit-identical). Like the export
        side, one traced-index scatter per page keeps every executable
        shape-stable across migrations."""
        jnp = _j().numpy
        self._ensure_pool()
        for name, attr in (("k", "_pk"), ("v", "_pv"),
                           ("sk", "_sk"), ("sv", "_sv")):
            leaves = getattr(self, attr)
            if leaves is None:
                if payload.get(name):
                    raise ValueError(
                        f"payload carries {name!r} planes but this engine "
                        f"has none (kv_dtype mismatch across replicas?)")
                continue
            blocks = payload[name]
            new = []
            for pool_l, per_page in zip(leaves, blocks):
                for p, blk in zip(pages, per_page):
                    pool_l = pool_l.at[jnp.asarray(p, jnp.int32)].set(
                        jnp.asarray(blk))
                new.append(pool_l)
            setattr(self, attr, self._place_migrated(tuple(new), name))

    def _place_migrated(self, leaves, name):  # noqa: ARG002
        """Placement seam after a migration write: the base engine keeps
        the eager scatter results as-is; the sharded engine re-pins them
        to the pool layout so donation aliasing still matches."""
        return leaves

    # -- shared attention helpers (traced) ----------------------------------

    def _dequant_view(self, pool_l, scale_l, idx):
        """Gather pages `idx` from one layer's pool and return the real-
        valued view ``(..., n_idx * page_tokens, d)`` (leading dims follow
        `idx`'s shape). fp pools gather straight through."""
        jnp = _j().numpy
        from ..ops.paged_attention import unpack_pages

        v = jnp.take(pool_l, idx, axis=0)
        v = unpack_pages(v, v.shape[-2] * v.shape[-1] // self.page_tokens)
        if self._int8:
            sc = jnp.take(scale_l, idx, axis=0)
            v = v.astype(jnp.float32) * sc[..., None, None]
        return v

    # -- chunked prefill ----------------------------------------------------

    def _build_prefill(self, dec=None, kind="prefill"):
        """Chunked-prefill program family for `dec` (default the target;
        the draft model gets its own family writing its own pools)."""
        jax = _j()
        jnp = jax.numpy
        lax = jax.lax
        dec = self._dec if dec is None else dec
        H = dec._n_heads
        pt = self.page_tokens
        int8 = self._int8

        from ..contrib.quantization import quantize_symmetric
        from ..models.decoding import _dense, _ln, _split_qkv
        from ..ops.paged_attention import pack_pages

        def to_pages(t):
            # (1, H, C, d) -> (C//pt pages, H, pt, d)
            _, _, C, d = t.shape
            return jnp.transpose(
                t[0].transpose(1, 0, 2).reshape(C // pt, pt, H, d),
                (0, 2, 1, 3))

        def run(params, pk, pv, sk, sv, tokens, pages_row, chunk_pages,
                t_start, t_len, key, temperature, top_k, do_sample):
            C = tokens.shape[1]
            PT = pages_row.shape[0] * pt
            pos_tab = params["pos"]
            pos_idx = jnp.clip(t_start + jnp.arange(C), 0,
                               pos_tab.shape[0] - 1)
            x = params["embed"][tokens] + pos_tab[pos_idx]
            qpos = t_start + jnp.arange(C)
            # causal-with-offset validity: key position j is visible to
            # chunk row i iff j <= t_start + i — this covers BOTH the
            # prefix pages (j < t_start) and in-chunk causality, and
            # masks stale/trash/padding pages in one stroke
            mask = jnp.arange(PT)[None, :] <= qpos[:, None]
            sm_scale = 1.0 / math.sqrt(dec._units // H)
            d = dec._units // H

            # Python-unrolled over layers: each iteration reads/writes
            # ITS OWN donated pool leaf, so XLA's donation map aliases
            # every leaf in place (a scan over a stacked pool re-stacks
            # the whole pool per call — the O(L × n_pages) rewrite this
            # layout exists to remove)
            L = len(pk)
            pk, pv = list(pk), list(pv)
            sk = list(sk) if int8 else [None] * L
            sv = list(sv) if int8 else [None] * L
            for li in range(L):
                lp = {n: a[li] for n, a in params["layers"].items()}
                pk_l, pv_l = pk[li], pv[li]
                sk_l, sv_l = sk[li], sv[li]
                h = _ln(x, lp["ln1_g"], lp["ln1_b"])
                q, k, v = _split_qkv(_dense(h, lp["qkv_w"], lp["qkv_b"]), H)
                kp, vp = to_pages(k), to_pages(v)
                if int8:
                    kq, ks = quantize_symmetric(kp, axes=(2, 3))
                    vq, vs = quantize_symmetric(vp, axes=(2, 3))
                    pk_l = pk_l.at[chunk_pages].set(kq)
                    pv_l = pv_l.at[chunk_pages].set(vq)
                    sk_l = sk_l.at[chunk_pages].set(ks[:, :, 0, 0])
                    sv_l = sv_l.at[chunk_pages].set(vs[:, :, 0, 0])
                else:
                    pk_l = pk_l.at[chunk_pages].set(
                        pack_pages(kp.astype(pk_l.dtype)))
                    pv_l = pv_l.at[chunk_pages].set(
                        pack_pages(vp.astype(pv_l.dtype)))
                # slot view: (P, H, pt, d) -> (1, H, P*pt, d)
                vk = self._dequant_view(pk_l, sk_l, pages_row)
                vv = self._dequant_view(pv_l, sv_l, pages_row)
                vk = jnp.transpose(vk, (1, 0, 2, 3)).reshape(H, PT, d)[None]
                vv = jnp.transpose(vv, (1, 0, 2, 3)).reshape(H, PT, d)[None]
                if int8:
                    # the chunk attends to its OWN K/V exactly (pre-
                    # quantization) — only the prefix pays quantization
                    vk = lax.dynamic_update_slice(vk, k.astype(vk.dtype),
                                                  (0, 0, t_start, 0))
                    vv = lax.dynamic_update_slice(vv, v.astype(vv.dtype),
                                                  (0, 0, t_start, 0))
                # mirror ops/flash_attention._xla_attention exactly (the
                # impl the unpaged GPTDecoder prefill resolves to at
                # serving sizes) so paged output stays bit-identical
                s = jnp.einsum("bhqd,bhkd->bhqk", q, vk) * sm_scale
                neg = jnp.asarray(jnp.finfo(s.dtype).min / 2, s.dtype)
                s = jnp.where(mask[None, None], s, neg)
                p = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("bhqk,bhkd->bhqd", p, vv)
                o = jnp.transpose(o, (0, 2, 1, 3)).reshape(1, C, H * d)
                x = x + _dense(o, lp["proj_w"], lp["proj_b"])
                h = _ln(x, lp["ln2_g"], lp["ln2_b"])
                ffn = _dense(
                    jax.nn.gelu(_dense(h, lp["ffn1_w"], lp["ffn1_b"])),
                    lp["ffn2_w"], lp["ffn2_b"])
                x = x + ffn
                pk[li], pv[li] = pk_l, pv_l
                sk[li], sv[li] = sk_l, sv_l
            pk, pv = tuple(pk), tuple(pv)
            sk = tuple(sk) if int8 else None
            sv = tuple(sv) if int8 else None
            # the chunk's last REAL row (padding beyond t_len is causally
            # downstream of it and cannot touch it)
            h_last = lax.dynamic_slice_in_dim(x, t_len - 1, 1,
                                              axis=1)[:, 0]
            logits = dec._logits(params, h_last)               # (1, V)
            first = dec._sample(logits, key, temperature, top_k, do_sample)
            pk, pv, sk, sv = self._constrain_pools(pk, pv, sk, sv)
            return pk, pv, sk, sv, first[0]

        # the int8 pools carry per-page scale planes as extra donated
        # state; the fp signature omits them entirely (donating an
        # unused placeholder would invalidate its buffer)
        if int8:
            def prefill(params, pk, pv, sk, sv, tokens, pages_row,
                        chunk_pages, t_start, t_len, key, temperature, *,
                        top_k, do_sample):
                return run(params, pk, pv, sk, sv, tokens, pages_row,
                           chunk_pages, t_start, t_len, key, temperature,
                           top_k, do_sample)

            return self._observed(
                jax.jit(prefill, static_argnames=("top_k", "do_sample"),
                        donate_argnums=(1, 2, 3, 4)),
                kind, donate=(1, 2, 3, 4), tokens_idx=5)

        def prefill(params, pk, pv, tokens, pages_row, chunk_pages,
                    t_start, t_len, key, temperature, *, top_k, do_sample):
            pk, pv, _, _, first = run(params, pk, pv, None, None, tokens,
                                      pages_row, chunk_pages, t_start,
                                      t_len, key, temperature, top_k,
                                      do_sample)
            return pk, pv, first

        return self._observed(
            jax.jit(prefill, static_argnames=("top_k", "do_sample"),
                    donate_argnums=(1, 2)),
            kind, donate=(1, 2), tokens_idx=3)

    def _observed(self, fn, kind, donate, tokens_idx=None):
        """Compile-observatory wrapper for a program family: recompiles
        past the first get forensics, and bucketed prefill growth (a new
        chunk bucket seen at `tokens_idx`) is classified `new_bucket`.
        `instrument_jit` passes `_cache_size` through, so
        `xla_program_count` and the shardcheck pre-flight see the raw
        jitted object's introspection surface."""
        bucket = None
        if tokens_idx is not None:
            def bucket(args, kwargs, _i=tokens_idx):  # noqa: ARG001
                return int(args[_i].shape[1])
        return _compiles.instrument_jit(
            fn, f"{self.census_name}.{kind}", bucket=bucket, donate=donate)

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
        """Run ONE page-aligned prefill chunk for `slot`.

        `chunk_tokens` is the 1D host slice ``prompt[t_start:t_start+n]``
        with ``t_start`` page-aligned (0 or a multiple of `page_tokens`,
        e.g. the shared-prefix boundary). The chunk is LAUNCHED, not waited
        for. Returns ``(first_token, bucket, pad)`` — the sampled token as
        the program gives it, a device scalar not yet fetched: it is
        meaningful only when this was the prompt's final chunk, and only
        then does the caller fetch it (``int(first)``, which blocks until
        the chunk ran); `bucket`/`pad` feed the caller's span
        annotations. `key` is a PRNG key or a callable that makes one: the
        scheduler hands its key maker in, so that the eager ``fold_in``
        runs inside the launch span with the rest of the host's work.
        """
        jnp = _j().numpy
        with tracing.phase("mx.serve.prefill.launch", "prefill_launch"):
            self._refresh_params()
            self._ensure_pool()
            if self._prefill_jit is None:
                self._prefill_jit = self._build_prefill()
            pt = self.page_tokens
            if t_start % pt:
                raise ValueError(
                    f"chunk start {t_start} is not page-aligned "
                    f"(page_tokens={pt})")
            chunk, n, bucket, pad = self._to_bucket(chunk_tokens)
            # the chunk's pages, padded with the trash page where the
            # bucket overshoots the slot's mapped range (pad-token K/V is
            # discarded)
            first_page = t_start // pt
            row = self._table[slot].copy()    # the launch's own (`_upload`)
            cp = bucket // pt
            chunk_pages = onp.zeros(cp, onp.int32)
            avail = row[first_page:first_page + cp]
            chunk_pages[:avail.size] = avail
            if callable(key):
                key = key()
            args = (jnp.asarray(chunk)[None, :], jnp.asarray(row),
                    jnp.asarray(chunk_pages), jnp.int32(t_start),
                    jnp.int32(n), key,
                    jnp.float32(max(float(temperature), 1e-6)))
            if self._int8:
                (self._pk, self._pv, self._sk, self._sv,
                 first) = self._prefill_jit(
                    self._dec._params, self._pk, self._pv, self._sk,
                    self._sv, *args, top_k=self._top_k,
                    do_sample=self._do_sample)
            else:
                self._pk, self._pv, first = self._prefill_jit(
                    self._dec._params, self._pk, self._pv, *args,
                    top_k=self._top_k, do_sample=self._do_sample)
            if self._draft_dec is not None:
                # the draft model prefills the SAME chunk into its own
                # pools (same pages — table/allocator are shared), so
                # spec drafting starts from a warm draft KV for every
                # request
                self._draft_dec._auto_refresh()
                if self._draft_prefill_jit is None:
                    self._draft_prefill_jit = self._build_prefill(
                        self._draft_dec, "draft_prefill")
                if self._int8:
                    (self._dpk, self._dpv, self._dsk, self._dsv,
                     _) = self._draft_prefill_jit(
                        self._draft_dec._params, self._dpk, self._dpv,
                        self._dsk, self._dsv, *args, top_k=self._top_k,
                        do_sample=self._do_sample)
                else:
                    self._dpk, self._dpv, _ = self._draft_prefill_jit(
                        self._draft_dec._params, self._dpk, self._dpv,
                        *args, top_k=self._top_k,
                        do_sample=self._do_sample)
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

    def _make_write_token(self):
        """Traced helper shared by the decode/verify/draft programs:
        scatter one token's K or V ``(S, H, d)`` at each slot's write
        page/offset; int8 re-quantizes just the written page under a
        grow-only scale."""
        jnp = _j().numpy
        int8 = self._int8
        S = self.max_slots

        from ..contrib.quantization import quantize_symmetric
        from ..ops.paged_attention import pack_pages, unpack_pages

        def write_token(pool_l, scale_l, wpage, woff, t):
            if not int8:
                # whole pages out, the token's row set, whole pages back:
                # a page is one contiguous block of the leaf, so the
                # update runs in place. (A scatter of (H, d) rows makes
                # the TPU's compiler turn the whole leaf to a layout with
                # H beside d, and back.)
                page = unpack_pages(jnp.take(pool_l, wpage, axis=0),
                                    t.shape[-1])               # (S,H,pt,d)
                row = jnp.arange(page.shape[2])[None, None, :, None]
                page = jnp.where(row == woff[:, None, None, None],
                                 t.astype(pool_l.dtype)[:, :, None, :], page)
                return pool_l.at[wpage].set(pack_pages(page)), scale_l
            old = jnp.take(scale_l, wpage, axis=0)             # (S, H)
            amax = jnp.max(jnp.abs(t), axis=-1)                # (S, H)
            new = jnp.maximum(old, jnp.maximum(amax, 1e-8) / 127.0)
            page = jnp.take(pool_l, wpage, axis=0)             # (S,H,pt,d)
            page = jnp.clip(
                jnp.round(page.astype(jnp.float32)
                          * (old / new)[:, :, None, None]),
                -127, 127)
            tq, _ = quantize_symmetric(t, axes=(), scale=new[:, :, None])
            page = page.at[jnp.arange(S), :, woff].set(tq)
            pool_l = pool_l.at[wpage].set(page.astype(jnp.int8))
            scale_l = scale_l.at[wpage].set(new)
            return pool_l, scale_l

        return write_token

    def _decode_layer_step(self, dec, lp, x, pools, table, wpage, woff,
                           lengths, write_token):
        """One layer of the single-token decode body — shared verbatim
        by the decode program and each unrolled step of the draft
        program so all three stay bit-identical. `pools` is the layer's
        ``(pk_l, pv_l, sk_l, sv_l)``; `lengths` is how many tokens of
        each slot the new token attends (its own included; 0 for a slot
        that does not decode); returns updated ``(x, pools)``."""
        jax = _j()
        from ..models.decoding import _dense, _ln, _split_qkv
        from ..ops.paged_attention import paged_decode_attention

        H = dec._n_heads
        d = dec._units // H
        S = self.max_slots
        pk_l, pv_l, sk_l, sv_l = pools
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        q, k, v = _split_qkv(_dense(h, lp["qkv_w"], lp["qkv_b"]), H)
        # the new token's K/V is in the pool before attention reads it
        pk_l, sk_l = write_token(pk_l, sk_l, wpage, woff, k[:, :, 0])
        pv_l, sv_l = write_token(pv_l, sv_l, wpage, woff, v[:, :, 0])
        with self._mesh_scope():
            o = paged_decode_attention(q[:, :, 0], pk_l, pv_l, table,
                                       lengths, k_scale=sk_l, v_scale=sv_l)
        x = x + _dense(o.reshape(S, 1, H * d), lp["proj_w"], lp["proj_b"])
        h = _ln(x, lp["ln2_g"], lp["ln2_b"])
        ffn = _dense(
            jax.nn.gelu(_dense(h, lp["ffn1_w"], lp["ffn1_b"])),
            lp["ffn2_w"], lp["ffn2_b"])
        return x + ffn, (pk_l, pv_l, sk_l, sv_l)

    def _build_decode(self):
        jax = _j()
        jnp = jax.numpy
        dec = self._dec
        pt = self.page_tokens
        int8 = self._int8
        S = self.max_slots
        write_token = self._make_write_token()

        def run(params, pk, pv, sk, sv, table, last_tok, prev_tok, pos,
                active, key, temperature, top_k, do_sample):
            # a slot that goes on from the launch before takes the token
            # that launch gave it, which the host may not have seen yet
            last_tok = jnp.where(last_tok < 0, prev_tok, last_tok)
            x = (params["embed"][last_tok][:, None, :]
                 + params["pos"][pos][:, None, :])              # (S, 1, C)
            # each slot writes at its own page/offset; slots that are
            # free or still prefilling are redirected to the trash page
            # and attend nothing
            wpage = table[jnp.arange(S), pos // pt]
            wpage = jnp.where(active, wpage, 0)
            woff = pos % pt
            lengths = jnp.where(active, pos + 1, 0)

            # unrolled over layers — each pool leaf aliases its donated
            # input (see _make_pools)
            L = len(pk)
            pk, pv = list(pk), list(pv)
            sk = list(sk) if int8 else [None] * L
            sv = list(sv) if int8 else [None] * L
            for li in range(L):
                lp = {n: a[li] for n, a in params["layers"].items()}
                x, (pk[li], pv[li], sk[li], sv[li]) = \
                    self._decode_layer_step(
                        dec, lp, x, (pk[li], pv[li], sk[li], sv[li]),
                        table, wpage, woff, lengths, write_token)
            pk, pv = tuple(pk), tuple(pv)
            sk = tuple(sk) if int8 else None
            sv = tuple(sv) if int8 else None
            logits = dec._logits(params, x[:, 0])               # (S, V)
            nxt = self._sample_slots(logits, key, temperature, top_k,
                                     do_sample)
            # free/prefilling slots carry their last token forward — the
            # host never reads them, but a defined value keeps the
            # program deterministic
            nxt = self._pin_tokens(jnp.where(active, nxt, last_tok))
            pk, pv, sk, sv = self._constrain_pools(pk, pv, sk, sv)
            return pk, pv, sk, sv, nxt

        if int8:
            def decode(params, pk, pv, sk, sv, table, last_tok, prev_tok,
                       pos, active, key, temperature, *, top_k, do_sample):
                return run(params, pk, pv, sk, sv, table, last_tok,
                           prev_tok, pos, active, key, temperature, top_k,
                           do_sample)

            return self._observed(
                jax.jit(decode, static_argnames=("top_k", "do_sample"),
                        donate_argnums=(1, 2, 3, 4)),
                "decode", donate=(1, 2, 3, 4))

        def decode(params, pk, pv, table, last_tok, prev_tok, pos, active,
                   key, temperature, *, top_k, do_sample):
            pk, pv, _, _, nxt = run(params, pk, pv, None, None, table,
                                    last_tok, prev_tok, pos, active, key,
                                    temperature, top_k, do_sample)
            return pk, pv, nxt

        return self._observed(
            jax.jit(decode, static_argnames=("top_k", "do_sample"),
                    donate_argnums=(1, 2)),
            "decode", donate=(1, 2))

    def _decode_args(self, last_tok, pos, active, key, temperature):
        """What a decode program takes after the pools: the table, the
        launch's own copies of the host's arrays, and the tokens of the
        launch before (`decode_step`)."""
        jnp = _j().numpy
        if callable(key):
            key = key()
        if self._tokens is None:
            self._tokens = self._pin_tokens(
                jnp.zeros(self.max_slots, jnp.int32))
        return (self._table_device(), _upload(last_tok, onp.int32),
                self._tokens, _upload(pos, onp.int32), _upload(active, bool),
                key, _upload(temperature, onp.float32))

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
        ``numpy.asarray`` of it is the one host sync of a step, and whoever
        needs the tokens makes it (`Scheduler._land`). `key`: a PRNG key, or
        a callable that makes one (called inside the launch span, as in
        `prefill_chunk_step`)."""
        with tracing.phase("mx.serve.decode.launch", "decode_launch"):
            self._refresh_params()
            self._ensure_pool()
            if self._decode_jit is None:
                self._decode_jit = self._build_decode()
            args = self._decode_args(last_tok, pos, active, key, temperature)
            if self._int8:
                (self._pk, self._pv, self._sk, self._sv,
                 self._tokens) = self._decode_jit(
                    self._dec._params, self._pk, self._pv, self._sk,
                    self._sv, *args, top_k=self._top_k,
                    do_sample=self._do_sample)
            else:
                self._pk, self._pv, self._tokens = self._decode_jit(
                    self._dec._params, self._pk, self._pv, *args,
                    top_k=self._top_k, do_sample=self._do_sample)
            on = onp.asarray(active, bool)
            live = int((onp.asarray(pos)[on] // self.page_tokens + 1).sum())
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
        jax = _j()
        jnp = jax.numpy
        dec = self._dec
        H = dec._n_heads
        pt = self.page_tokens
        int8 = self._int8
        S = self.max_slots
        K1 = self.spec_k + 1
        write_token = self._make_write_token()

        from ..models.decoding import _dense, _ln, _split_qkv

        def run(params, pk, pv, sk, sv, table, toks, pos, active, limit):
            P = table.shape[1]
            PT = P * pt
            d = dec._units // H
            offs = jnp.arange(K1)
            p_abs = pos[:, None] + offs[None, :]               # (S, K1)
            pmax = params["pos"].shape[0]
            x = (params["embed"][toks]
                 + params["pos"][jnp.clip(p_abs, 0, pmax - 1)])
            writable = active[:, None] & (p_abs <= limit[:, None])
            wpage = jnp.take_along_axis(
                table, jnp.clip(p_abs // pt, 0, P - 1), axis=1)
            wpage = jnp.where(writable, wpage, 0)
            woff = p_abs % pt
            # (S, K1, PT) causal-per-row validity
            mask = jnp.arange(PT)[None, None, :] <= p_abs[:, :, None]

            L = len(pk)
            pk, pv = list(pk), list(pv)
            sk = list(sk) if int8 else [None] * L
            sv = list(sv) if int8 else [None] * L
            for li in range(L):
                lp = {n: a[li] for n, a in params["layers"].items()}
                pk_l, pv_l = pk[li], pv[li]
                sk_l, sv_l = sk[li], sv[li]
                h = _ln(x, lp["ln1_g"], lp["ln1_b"])
                q, k, v = _split_qkv(
                    _dense(h, lp["qkv_w"], lp["qkv_b"]), H)    # (S,H,K1,d)
                kt = jnp.transpose(k, (0, 2, 1, 3))            # (S,K1,H,d)
                vt = jnp.transpose(v, (0, 2, 1, 3))
                # column-at-a-time writes reuse the decode write_token
                # exactly (int8 grow-only rescale order preserved)
                for i in range(K1):
                    pk_l, sk_l = write_token(pk_l, sk_l, wpage[:, i],
                                             woff[:, i], kt[:, i])
                    pv_l, sv_l = write_token(pv_l, sv_l, wpage[:, i],
                                             woff[:, i], vt[:, i])
                vk = self._dequant_view(pk_l, sk_l, table)
                vv = self._dequant_view(pv_l, sv_l, table)
                vk = jnp.transpose(vk, (0, 2, 1, 3, 4)).reshape(S, H, PT, d)
                vv = jnp.transpose(vv, (0, 2, 1, 3, 4)).reshape(S, H, PT, d)
                s = jnp.einsum("shqd,shkd->shqk", q, vk,
                               preferred_element_type=jnp.float32)
                s = s / math.sqrt(d)
                s = jnp.where(mask[:, None, :, :], s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1).astype(vv.dtype)
                o = jnp.einsum("shqk,shkd->shqd", p, vv)
                o = jnp.transpose(o, (0, 2, 1, 3)).reshape(S, K1, H * d)
                x = x + _dense(o, lp["proj_w"], lp["proj_b"])
                h = _ln(x, lp["ln2_g"], lp["ln2_b"])
                ffn = _dense(
                    jax.nn.gelu(_dense(h, lp["ffn1_w"], lp["ffn1_b"])),
                    lp["ffn2_w"], lp["ffn2_b"])
                x = x + ffn
                pk[li], pv[li] = pk_l, pv_l
                sk[li], sv[li] = sk_l, sv_l
            pk, pv = tuple(pk), tuple(pv)
            sk = tuple(sk) if int8 else None
            sv = tuple(sv) if int8 else None
            logits = dec._logits(
                params, x.reshape(S * K1, -1)).reshape(S, K1, -1)
            tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tgt = jnp.where(active[:, None], tgt, toks)
            pk, pv, sk, sv = self._constrain_pools(pk, pv, sk, sv)
            return pk, pv, sk, sv, tgt

        if int8:
            def verify(params, pk, pv, sk, sv, table, toks, pos, active,
                       limit):
                return run(params, pk, pv, sk, sv, table, toks, pos,
                           active, limit)

            return self._observed(
                jax.jit(verify, donate_argnums=(1, 2, 3, 4)),
                "verify", donate=(1, 2, 3, 4))

        def verify(params, pk, pv, table, toks, pos, active, limit):
            pk, pv, _, _, tgt = run(params, pk, pv, None, None, table,
                                    toks, pos, active, limit)
            return pk, pv, tgt

        return self._observed(
            jax.jit(verify, donate_argnums=(1, 2)),
            "verify", donate=(1, 2))

    def _build_draft(self):
        """ONE draft-model program: k unrolled greedy decode steps
        (each step identical in structure to the decode program, against
        the draft's own per-layer pools) — k drafted tokens per launch,
        feeding the target's verify program."""
        jax = _j()
        jnp = jax.numpy
        dec = self._draft_dec
        pt = self.page_tokens
        int8 = self._int8
        S = self.max_slots
        K = self.spec_k
        write_token = self._make_write_token()

        def run(params, pk, pv, sk, sv, table, last_tok, pos, active,
                limit):
            P = table.shape[1]
            pmax = params["pos"].shape[0]
            L = len(pk)
            pk, pv = list(pk), list(pv)
            sk = list(sk) if int8 else [None] * L
            sv = list(sv) if int8 else [None] * L
            cur = last_tok
            outs = []
            for i in range(K):
                p_i = pos + i
                wpage = table[jnp.arange(S), jnp.clip(p_i // pt, 0, P - 1)]
                wpage = jnp.where(active & (p_i <= limit), wpage, 0)
                woff = p_i % pt
                lengths = jnp.where(active, p_i + 1, 0)
                x = (params["embed"][cur][:, None, :]
                     + params["pos"][jnp.clip(p_i, 0, pmax - 1)][:, None, :])
                for li in range(L):
                    lp = {n: a[li] for n, a in params["layers"].items()}
                    x, (pk[li], pv[li], sk[li], sv[li]) = \
                        self._decode_layer_step(
                            dec, lp, x, (pk[li], pv[li], sk[li], sv[li]),
                            table, wpage, woff, lengths, write_token)
                logits = dec._logits(params, x[:, 0])
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                cur = jnp.where(active, nxt, cur)
                outs.append(cur)
            pk, pv = tuple(pk), tuple(pv)
            sk = tuple(sk) if int8 else None
            sv = tuple(sv) if int8 else None
            pk, pv, sk, sv = self._constrain_pools(pk, pv, sk, sv)
            return pk, pv, sk, sv, jnp.stack(outs, axis=1)      # (S, K)

        if int8:
            def draft(params, pk, pv, sk, sv, table, last_tok, pos,
                      active, limit):
                return run(params, pk, pv, sk, sv, table, last_tok, pos,
                           active, limit)

            return self._observed(
                jax.jit(draft, donate_argnums=(1, 2, 3, 4)),
                "draft", donate=(1, 2, 3, 4))

        def draft(params, pk, pv, table, last_tok, pos, active, limit):
            pk, pv, _, _, toks = run(params, pk, pv, None, None, table,
                                     last_tok, pos, active, limit)
            return pk, pv, toks

        return self._observed(
            jax.jit(draft, donate_argnums=(1, 2)),
            "draft", donate=(1, 2))

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

    def spec_draft_step(self, last_tok, pos, active, limit):
        """Run the draft model's k-step program; returns drafted tokens
        ``(max_slots, spec_k)`` as host numpy."""
        jnp = _j().numpy
        with tracing.phase("mx.serve.spec.draft.launch", "decode_launch"):
            self._draft_dec._auto_refresh()
            self._ensure_pool()
            if self._draft_jit is None:
                self._draft_jit = self._build_draft()
            args = (self._table_device(),
                    jnp.asarray(last_tok, jnp.int32),
                    jnp.asarray(pos, jnp.int32),
                    jnp.asarray(active, bool),
                    jnp.asarray(limit, jnp.int32))
            if self._int8:
                (self._dpk, self._dpv, self._dsk, self._dsv,
                 toks) = self._draft_jit(
                    self._draft_dec._params, self._dpk, self._dpv,
                    self._dsk, self._dsv, *args)
            else:
                self._dpk, self._dpv, toks = self._draft_jit(
                    self._draft_dec._params, self._dpk, self._dpv, *args)
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
        jnp = _j().numpy
        with tracing.phase("mx.serve.spec.verify.launch", "decode_launch"):
            self._refresh_params()
            self._ensure_pool()
            if self._verify_jit is None:
                self._verify_jit = self._build_verify()
            if not self._spec_gauge:
                self._register_spec_gauge()
            toks = onp.concatenate(
                [onp.asarray(last_tok, onp.int32)[:, None],
                 onp.asarray(drafts, onp.int32)], axis=1)
            args = (self._table_device(),
                    jnp.asarray(toks),
                    jnp.asarray(pos, jnp.int32),
                    jnp.asarray(active, bool),
                    jnp.asarray(limit, jnp.int32))
            if self._int8:
                (self._pk, self._pv, self._sk, self._sv,
                 tgt) = self._verify_jit(
                    self._dec._params, self._pk, self._pv, self._sk,
                    self._sv, *args)
            else:
                self._pk, self._pv, tgt = self._verify_jit(
                    self._dec._params, self._pk, self._pv, *args)
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
        idx = jnp.asarray(self._table[slot])
        outs = []
        for pool, scale in ((self._pk, self._sk), (self._pv, self._sv)):
            views = []
            L = len(pool)
            for layer in range(L):
                v = self._dequant_view(pool[layer],
                                       None if scale is None
                                       else scale[layer], idx)
                P, H, pt, d = v.shape
                views.append(jnp.transpose(v, (1, 0, 2, 3))
                             .reshape(H, P * pt, d)[:, :n_tokens])
            outs.append(onp.asarray(jnp.stack(views), onp.float32))
        return outs[0], outs[1]

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

        The engine runs single-chip today, so with the default
        ``mesh=None`` this is a per-device byte budget (SC006) plus the
        donation audit (SC004); pass a mesh once pod-scale serving lands
        and the same call re-validates the layout against it. Returns
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
        params = self._dec._params
        pools = (self._pk, self._pv) + ((self._sk, self._sv)
                                        if self._int8 else ())
        donate = (1, 2, 3, 4) if self._int8 else (1, 2)
        S = self.max_slots
        key = next_key()
        i32, f32 = _j().numpy.int32, _j().numpy.float32
        statics = {"top_k": self._top_k, "do_sample": self._do_sample}

        bucket = int(bucket) if bucket is not None else self.chunk_buckets[-1]
        head_specs = self._shardcheck_specs()
        out_specs = self._shardcheck_out_specs()
        prefill_args = (params,) + pools + (
            sds((1, bucket), i32),                      # tokens
            sds((self.pages_per_slot,), i32),           # pages_row
            sds((bucket // self.page_tokens,), i32),    # chunk_pages
            sds((), i32), sds((), i32),                 # t_start, t_len
            key, sds((), f32))                          # key, temperature
        pf_specs = None if head_specs is None else head_specs + (
            (None,) * (len(prefill_args) - len(head_specs)))
        prefill = shardcheck(
            functools.partial(self._prefill_jit, **statics), *prefill_args,
            mesh=mesh, specs=pf_specs, out_specs=out_specs,
            donate_argnums=donate, hbm_budget_gb=hbm_budget_gb,
            name=f"SlotDecoder.prefill[b{bucket}]")

        decode_args = (params,) + pools + (
            sds((S, self.pages_per_slot), i32),         # page table
            sds((S,), i32), sds((S,), i32),             # last_tok, prev_tok
            sds((S,), i32), sds((S,), bool),            # pos, active
            key, sds((S,), f32))                        # key, temperature
        dc_specs = None if head_specs is None else head_specs + (
            (None,) * (len(decode_args) - len(head_specs)))
        decode = shardcheck(
            functools.partial(self._decode_jit, **statics), *decode_args,
            mesh=mesh, specs=dc_specs, out_specs=out_specs,
            donate_argnums=donate, hbm_budget_gb=hbm_budget_gb,
            hot_path=True, name="SlotDecoder.decode")
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
