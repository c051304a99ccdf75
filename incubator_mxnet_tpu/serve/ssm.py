"""`mx.serve` for the hybrid state-space family (`models/nemotron_h.py`):
fixed-size recurrent state per slot BESIDE pages.

The decoder's `layer_kinds` says what each block keeps in a slot. Here most
blocks are Mamba-2 mixers that keep **state** — the recurrence's ``(H, P, N)``
float32 and the convolution's tail of ``kernel - 1`` rows, 4.26 MB a slot a
layer at the published sizes, whatever the context —, one block in eleven is
attention and keeps **pages** (grouped heads: two stored heads a token, 1 KB),
and the expert layers keep nothing. So the pools pytree that every program
takes donated and returns has, beside ``"k"`` / ``"v"`` with ONE leaf an
attention block (`kv_geometry()[0]`: the pool is not multiplied by the
blocks), the leaves ``"ssm"`` (``max_slots`` x a slot's ``(H, P, N)`` as
`ops.ssm.state_store_shape` lays it out) and ``"conv"``
``(max_slots, kernel - 1, channels)``, one of each a Mamba block
(`state_geometry`). They are indexed by SLOT, not by page: the allocator, the
page table and `pages_needed` know nothing of them.

**The state's life.** Zeroed when a slot is given to a request: the request's
first chunk (``t_start == 0``) reads zeros in place of what the slot's last
request left — inside the chunk program, no launch of its own (counted on the
host: ``mx_serve_state_slot_resets_total``, ``state_resets`` in the step
record). Carried from chunk to chunk: a chunk reads its slot's state, runs the
chunked scan (`ops.ssm.ssm_chunk`; a bucket's padding rows change nothing) and
writes it back. Advanced in place by every decode step for active slots only
(`ops.ssm.ssm_decode`, the kernel ``mx_ssm_decode``): a free or prefilling
slot's state is bit for bit what it was. Dropped with the pools (`release`).
``mx_serve_state_bytes`` says what it takes.

**What it is worthless to.** A page holds exact rows of known positions and
can be shared or moved; a state is the whole prefix folded together, and a
prefix's pages are not a prefix's state. Refused, each with
`NotImplementedError` that names the family: ``prefix_reuse=True`` (a cached
prefix would need the state AT its end: snapshots are not kept), int8 pages,
speculative decoding (a rejected draft would have to roll the state back),
prefill-only handoff and adoption (`copy_pages_out/in` move pages only),
`Scheduler.preempt` (it parks a request's pages in the prefix cache; the
state would be lost and the resume silently wrong) and the sharded engine.

The expert layers' counts come home in the tokens' fetch, as
`serve/mla.py`'s (`ExpertStepCounts`: ``mx_serve_moe_pairs_total``,
``mx_serve_moe_experts_hit_total``, the step record's ``moe_*``).
"""
from __future__ import annotations

import math
import os
import weakref

import numpy as onp

from ..models.nemotron_h import NemotronHDecoder
from ..telemetry import registry, tracing
from .engine import SlotDecoder, _j
from .mla import CHUNK_BLOCK_ROWS, ExpertStats, ExpertStepCounts
from .pages import PageCache, TokenCache

__all__ = ["HybridSlotDecoder"]

_NEG = -1.0e30

STATE_RESETS = registry.counter(
    "mx_serve_state_slot_resets_total",
    "slots whose recurrent state was zeroed for a new request (in its "
    "first prefill chunk's program)")


class _TokenCache(ExpertStats, TokenCache):
    """A decode step: one new row a slot. Pages as `TokenCache` has them
    (grouped heads); every slot's state advanced in place where active."""

    step = "decode"

    def __init__(self, eng, pools, table, col, woff, rows, active):
        TokenCache.__init__(self, eng, pools, table, col, woff, rows, active)
        self.valid = active

    def mix(self, nth, fn):
        """``fn(state (S, ...as stored), tail (S, K - 1, C)) -> (y,
        state', tail')`` over the `nth` Mamba block's leaves."""
        ssm, conv = self.leaves["ssm"], self.leaves["conv"]
        y, ssm[nth], conv[nth] = fn(ssm[nth], conv[nth])
        return y


class _ChunkCache(ExpertStats, PageCache):
    """One prefill chunk of one slot: its rows go into `chunk_pages` and
    its queries attend the slot's rows up to the chunk's end, a block of
    pages at a time; its Mamba blocks read the slot's state (zeros where the
    chunk is the request's first) and write it back."""

    step = "chunk"

    def __init__(self, eng, pools, pages_row, chunk_pages, slot, t_start,
                 t_len):
        jnp = _j().numpy
        super().__init__(eng, pools)
        self.pages_row, self.chunk_pages = pages_row, chunk_pages
        self.slot, self.t_start, self.t_len = slot, t_start, t_len
        self.valid = jnp.arange(
            chunk_pages.shape[0] * eng.page_tokens) < t_len
        want = max(1, CHUNK_BLOCK_ROWS // eng.page_tokens)
        n = pages_row.shape[0]
        self.block_pages = max(g for g in range(1, min(want, n) + 1)
                               if n % g == 0)

    def mix(self, nth, fn):
        """``fn(state (as stored), tail (K - 1, C)) -> (y, state',
        tail')`` over this slot's part of the `nth` Mamba block's leaves."""
        jax = _j()
        jnp = jax.numpy
        fresh = self.t_start == 0
        own = []
        for kind in ("ssm", "conv"):
            a = jax.lax.dynamic_index_in_dim(self.leaves[kind][nth],
                                             self.slot, 0, keepdims=False)
            own.append(jnp.where(fresh, jnp.zeros_like(a), a))
        y, *own = fn(*own)
        for kind, a in zip(("ssm", "conv"), own):
            leaf = self.leaves[kind]
            leaf[nth] = jax.lax.dynamic_update_index_in_dim(
                leaf[nth], a.astype(leaf[nth].dtype), self.slot, 0)
        return y

    def attend(self, li, q, k, v):
        """``q`` (T, Hq, d), ``k`` / ``v`` (T, Hk, d); returns (T, Hq, d)."""
        jax = _j()
        jnp = jax.numpy
        pt, bp = self.eng.page_tokens, self.block_pages
        t, hq, d = q.shape
        hk = k.shape[1]

        def to_pages(a):            # (T, Hk, d) -> (T // pt, Hk, pt, d)
            return jnp.transpose(a.reshape(t // pt, pt, hk, d), (0, 2, 1, 3))

        self.write_pages(li, self.chunk_pages, to_pages(k), to_pages(v))
        dt, f32 = self.leaves["k"][li].dtype, jnp.float32
        qg = jnp.transpose(q.reshape(t, hk, hq // hk, d), (1, 2, 0, 3))
        qg = (qg * (1.0 / math.sqrt(d))).astype(dt)        # (Hk, rep, T, d)
        qpos = self.t_start + jnp.arange(t)
        rows = bp * pt

        def block(b, carry):
            m, l, acc = carry
            idx = jax.lax.dynamic_slice_in_dim(self.pages_row, b * bp, bp)
            kb, vb = self.rows(li, idx)                    # (Hk, rows, d)
            s = jnp.einsum("hrtd,hkd->hrtk", qg, kb.astype(dt),
                           preferred_element_type=f32)
            seen = (b * rows + jnp.arange(rows))[None, :] <= qpos[:, None]
            s = jnp.where(seen[None, None], s, _NEG)
            # block 0 holds position 0, which every query sees: from there
            # on `m` is a real score and a masked one's weight is exp(-1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            return (m_new, alpha * l + jnp.sum(p, axis=-1),
                    alpha[..., None] * acc + jnp.einsum(
                        "hrtk,hkd->hrtd", p.astype(dt), vb.astype(dt),
                        preferred_element_type=f32))

        n_blocks = (self.t_start + t + rows - 1) // rows
        shape = (hk, hq // hk, t)
        _, l, acc = jax.lax.fori_loop(
            0, n_blocks, block,
            (jnp.full(shape, _NEG, f32), jnp.zeros(shape, f32),
             jnp.zeros(shape + (d,), f32)))
        return jnp.transpose(acc / l[..., None], (2, 0, 1, 3)).reshape(
            t, hq, d)


class HybridSlotDecoder(ExpertStepCounts, SlotDecoder):
    """Paged slot decoder over a `NemotronHDecoder` (see the module
    docstring). Parameters as `SlotDecoder`'s; `max_len` defaults to the
    model's ``max_position_embeddings``."""

    #: a request's pages are not its whole state: nothing that moves or
    #: parks pages alone is served (the scheduler asks)
    page_handoff = False
    preempt_refusal = (
        "the nemotron_h family is not served with preemption: "
        "`Scheduler.preempt` parks a request's pages in the prefix cache, "
        "and a prefix's pages are not a prefix's recurrent state (state "
        "snapshots are not kept)")

    def __init__(self, source, max_slots=8, max_len=None, page_tokens=None,
                 prefill_chunk=None, n_pages=None, kv_dtype=None,
                 prefix_reuse=None, do_sample=False, top_k=None,
                 spec_k=None, draft=None):
        from ..util import env_int

        def refuse(what, why):
            raise NotImplementedError(
                f"the nemotron_h family is not served with {what}: {why}")

        if spec_k is None:
            spec_k = env_int("MXNET_SERVE_SPEC_K", 0)
        if spec_k or draft is not None:
            refuse("speculative decoding (spec_k > 0, draft)",
                   "a rejected draft would have to roll the recurrent state "
                   "back, and the release's multi-token-prediction module "
                   "is not held")
        if kv_dtype is None:
            kv_dtype = os.environ.get("MXNET_SERVE_KV_DTYPE", "fp")
        if kv_dtype != "fp":
            refuse(f"kv_dtype={kv_dtype!r}",
                   "int8 pages beside a float32 recurrent state are not "
                   "worked out (one block in eleven has pages at all)")
        if prefix_reuse:
            refuse("prefix_reuse=True",
                   "a prefix's pages are not a prefix's recurrent state: a "
                   "shared prefix would need the state at its end, and "
                   "snapshots are not kept")
        super().__init__(source, max_slots=max_slots, max_len=max_len,
                         page_tokens=page_tokens, prefill_chunk=prefill_chunk,
                         n_pages=n_pages, kv_dtype="fp", prefix_reuse=False,
                         do_sample=do_sample, top_k=top_k, spec_k=0)
        self.chunk_buckets = self._quarter_and_whole_buckets()
        block = self._dec.config.chunk_size
        for b in self.chunk_buckets:
            if b > block and b % block:
                raise ValueError(
                    f"a prefill bucket of {b} rows is not whole blocks of "
                    f"the scan's chunk_size {block}")
        self._expert_layers = self._dec.expert_layers
        self._experts_per_tok = self._dec.config.num_experts_per_tok
        self.step_extra = 2 * self._expert_layers + 1
        ref = weakref.ref(self)
        registry.register_pull_gauge(
            "mx_serve_state_bytes",
            lambda: None if ref() is None else ref().state_bytes,
            "device bytes of the slots' recurrent state (state-space "
            "layers: fixed a slot, whatever the context), 0 if released")

    def _resolve_decoder(self, source):
        if not isinstance(source, NemotronHDecoder):
            raise TypeError("HybridSlotDecoder needs a NemotronHDecoder, got "
                            f"{type(source).__name__}")
        return source

    # -- state beside pages ---------------------------------------------------

    def _make_pools(self, dec):
        """The page leaves of the attention blocks, and a state leaf of
        each kind a Mamba block, ``(max_slots,) + a slot's shape``, zeroed."""
        jnp = _j().numpy
        pools = super()._make_pools(dec)
        layers, kinds = dec.state_geometry()
        for kind, (shape, dtype) in kinds.items():
            pools[kind] = tuple(jnp.zeros((self.max_slots,) + shape, dtype)
                                for _ in range(layers))
        return pools

    @property
    def state_bytes(self):
        """Device bytes of the slots' recurrent state (0 if released)."""
        if self._pools is None:
            return 0
        return sum(a.size * a.dtype.itemsize
                   for kind in self._dec.state_geometry()[1]
                   for a in self._pools[kind])

    def _pool_detail(self):
        return {"state_bytes": self.state_bytes}

    # -- the cache objects ----------------------------------------------------

    def _token_cache(self, pools, table, pos, active):
        return _TokenCache(self, pools, table, *self._row_of(pos), active)

    def _chunk_pages(self, slot, t_start, bucket):
        """`SlotDecoder`'s, and the slot itself: where its state lies."""
        return super()._chunk_pages(slot, t_start, bucket) \
            + (_j().numpy.int32(slot),)

    def _chunk_cache(self, pools, pages, t_start, t_len):
        return _ChunkCache(self, pools, *pages, t_start, t_len)

    def prefill_chunk_step(self, slot, chunk_tokens, t_start, key,
                           temperature=1.0):
        out = super().prefill_chunk_step(slot, chunk_tokens, t_start, key,
                                         temperature)
        if int(t_start) == 0:       # the program zeroed the slot's state
            STATE_RESETS.inc()
            tracing.count(state_resets=1)
        return out

    # -- debug / tests --------------------------------------------------------

    def slot_state(self, slot):
        """Host copies of a slot's state: ``{"ssm": (layers, H, P, N),
        "conv": (layers, K - 1, C)}`` float32."""
        from ..ops.ssm import unpack_state

        self._ensure_pool()
        out = {kind: onp.stack([onp.asarray(a[slot], onp.float32)
                                for a in self._pools[kind]])
               for kind in self._dec.state_geometry()[1]}
        out["ssm"] = onp.asarray(unpack_state(
            out["ssm"], self._dec.config.mamba_head_dim))
        return out
