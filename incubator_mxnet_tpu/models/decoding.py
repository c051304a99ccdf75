"""KV-cache incremental decoding for causal LMs (the serving path).

Reference role: the reference deploys frozen graphs through the
cpp-package `Predictor` (`cpp-package/include/mxnet-cpp/`), and the
GPT-2 generation of its era (GluonNLP) re-ran the full forward per
token. TPU-native design instead compiles the WHOLE decode as one XLA
program:

- a static-shape KV cache `(L, N, H, max_length, d)` — no growing
  shapes, so there is exactly ONE compile per (batch, prompt-bucket,
  max_new_tokens) signature, not one per decoded length;
- prefill = one causal flash-attention pass over the prompt that also
  writes the prompt's K/V into the cache;
- decode = `lax.scan` over steps; each step runs a single-token forward,
  layer by layer, against the cache (O(T) work per token instead of the
  O(T²) full re-forward) and samples the next token in-graph;
- sampling (temperature / top-k) uses the framework RNG key so
  `mx.random.seed` reproduces generations.

The layer math mirrors `GPTModel.forward` exactly (pre-norm blocks,
gelu FFN, tied LM head) — greedy decode emits the same tokens as the
eager full-forward loop, asserted by `tests/test_gpt.py`. It is written
once, `GPTDecoder.layer`, against a cache-access object: `generate` hands
it two small dense caches (`_PromptCache`, `_StepCache`), `mx.serve`'s
programs their paged ones (`serve/pages.py`), and `generate` is the
reference the engine's tests compare those programs against.
"""
from __future__ import annotations

import functools
import logging
import math

import numpy as onp

__all__ = ["GPTDecoder", "NgramProposer", "bucket_prompt",
           "PROMPT_BUCKETS", "chunk_buckets", "bucket_chunk"]

_LOG = logging.getLogger("incubator_mxnet_tpu.models")

#: Default pad-to-bucket prompt lengths. Ad-hoc prompt lengths each
#: compile their own XLA program (the signature includes the prompt
#: width); snapping to power-of-two buckets bounds the program count at
#: len(PROMPT_BUCKETS) per (batch, max_new) — the waste is padding
#: tokens, which `mx_decode_bucket_pad_tokens_total` makes visible.
PROMPT_BUCKETS = (32, 64, 128, 256, 512)


def bucket_prompt(ids, buckets=PROMPT_BUCKETS, max_len=None, pad_id=0):
    """Pad token ids (N, T) to the smallest bucket >= T.

    Returns ``(padded_ids, t0)`` where ``t0`` is the true prompt length.
    Padding goes on the RIGHT with `pad_id`; the padded positions' K/V
    are causally invisible to the last real token and are overwritten by
    decode before the attention mask ever reaches them, so any valid
    token id works as filler. Prompts longer than every bucket are
    returned unpadded (exact-length compile, the pre-bucketing
    behavior); `max_len` (when given) caps the chosen bucket.

    Pads with host/device-agnostic `jnp.pad`; the padding waste is
    accounted in the ``mx_decode_bucket_pad_tokens_total`` counter.
    """
    jnp = _j().numpy
    ids = jnp.asarray(ids)
    if ids.ndim != 2:
        raise ValueError(f"bucket_prompt expects (N, T) ids, got "
                         f"shape {ids.shape}")
    n, t0 = ids.shape
    fits = sorted(b for b in buckets
                  if b >= t0 and (max_len is None or b <= max_len))
    if not fits:
        return ids, t0
    bucket = fits[0]
    if bucket == t0:
        return ids, t0
    padded = jnp.pad(ids, ((0, 0), (0, bucket - t0)),
                     constant_values=pad_id)
    from ..telemetry import registry

    registry.counter(
        "mx_decode_bucket_pad_tokens_total",
        "prompt tokens added by pad-to-bucket in the decode/serving "
        "path (padding waste)").inc(int(n * (bucket - t0)))
    return padded, t0


def chunk_buckets(page_tokens, prefill_chunk):
    """Static chunk-length buckets for the paged serving prefill
    (`serve.SlotDecoder`): power-of-two multiples of `page_tokens` up to
    `prefill_chunk`, plus `prefill_chunk` itself. Every chunk is a whole
    number of pages, so chunk writes land on page boundaries and the
    compiled chunk-prefill family stays bounded at len(buckets) programs.
    """
    pt = int(page_tokens)
    chunk = int(prefill_chunk)
    if pt < 1:
        raise ValueError(f"page_tokens must be >= 1, got {pt}")
    if chunk % pt:
        raise ValueError(
            f"prefill_chunk ({chunk}) must be a multiple of page_tokens "
            f"({pt}) so chunks stay page-aligned")
    out = set()
    b = pt
    while b < chunk:
        out.add(b)
        b *= 2
    out.add(chunk)
    return tuple(sorted(out))


def bucket_chunk(n, buckets):
    """Smallest chunk bucket >= n (the last prefill chunk of a prompt is
    padded up to it; the waste rides the same
    ``mx_decode_bucket_pad_tokens_total`` counter as prompt bucketing)."""
    fits = [b for b in buckets if b >= n]
    if not fits:
        raise ValueError(f"chunk of {n} tokens exceeds every bucket "
                         f"{tuple(buckets)}")
    return min(fits)


def _j():
    import jax

    return jax


def _ln(x, g, b, eps=1e-5):
    """float32-internal layer norm matching `npx.layer_norm`."""
    jnp = _j().numpy
    xd = x.dtype
    x32 = x.astype(jnp.float32)
    mu = x32.mean(axis=-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(xd)


def _dense(x, w, b=None):
    """`npx.fully_connected(flatten=False)` over a matrix the decoder holds
    ``(in, out)``: y = x @ W (+ b)."""
    y = x @ w
    return y if b is None else y + b


def _split_qkv(h, n_heads):
    """(N, T, 3C) -> three (N, H, T, d), matching the gluon reshape."""
    jnp = _j().numpy
    N, T, C3 = h.shape
    C = C3 // 3
    d = C // n_heads
    qkv = h.reshape(N, T, 3, n_heads, d)
    q = jnp.transpose(qkv[:, :, 0], (0, 2, 1, 3))
    k = jnp.transpose(qkv[:, :, 1], (0, 2, 1, 3))
    v = jnp.transpose(qkv[:, :, 2], (0, 2, 1, 3))
    return q, k, v


#: the chip's lanes: it keeps a 2-D array row-major where the last axis is a
#: multiple of this, else whichever way round pads less (`_rows`)
_LANES = 128


def _own(leaves):
    """`leaves` of a Gluon block as buffers of the caller's own: a matrix
    ``(out, in)`` comes back as the transposed copy ``(in, out)``, a vector
    as a copy."""
    jnp = _j().numpy
    return {n: a.T if a.ndim == 2 else jnp.copy(a) for n, a in leaves.items()}


def _rows(leaves):
    """The tables ``(n, C)`` (rows are gathered from them; the logits
    contract over their last axis), their rows padded with zeros to whole
    lanes. The chip keeps such an array row-major; GPT-2 XL's ``(50257,
    1600)`` it keeps column-major (less padding), and every step's gather
    then begins with a re-layout of all 322 MB (`PERF.md` §6, PR 32)."""
    jnp = _j().numpy
    return {n: jnp.pad(a, ((0, 0), (0, -a.shape[1] % _LANES)))
            for n, a in leaves.items()}


@functools.cache
def _stored(form):
    """`_own` / `_rows` as one jitted call over a dict of leaves (a layer's
    twelve are one dispatch, not twelve)."""
    from ..telemetry.compiles import ledgered_jit

    return ledgered_jit(form, family=f"gpt.params.{form.__name__.strip('_')}")


class _PromptCache:
    """`GPTDecoder.generate`'s prefill as a cache-access object: causal
    flash attention over the whole prompt; keeps its ``k, v`` padded to
    `cache_len` rows for the decode steps."""

    def __init__(self, cache_len):
        self.cache_len = cache_len

    def attend(self, li, q, k, v):  # noqa: ARG002
        jnp = _j().numpy
        from ..ops.flash_attention import flash_attention

        o = flash_attention(q, k, v, causal=True,
                            sm_scale=1.0 / math.sqrt(q.shape[-1]))
        pad = [(0, 0), (0, 0), (0, self.cache_len - q.shape[2]), (0, 0)]
        self.k, self.v = jnp.pad(k, pad), jnp.pad(v, pad)
        return jnp.transpose(o, (0, 2, 1, 3))


class _StepCache:
    """One decode step of `GPTDecoder.generate` against one layer's dense
    cache ``k, v`` (N, H, cache_len, d): the token's row is written at
    `pos` and its query attends rows ``0 .. pos``."""

    def __init__(self, k, v, pos):
        self.k, self.v, self.pos = k, v, pos

    def attend(self, li, q, k, v):  # noqa: ARG002
        jax = _j()
        jnp = jax.numpy
        # write this token's k/v at position pos (static-shape update)
        ck = self.k = jax.lax.dynamic_update_slice(self.k, k,
                                                   (0, 0, self.pos, 0))
        cv = self.v = jax.lax.dynamic_update_slice(self.v, v,
                                                   (0, 0, self.pos, 0))
        # attend to positions 0..pos; later slots hold zeros/garbage that
        # the mask excludes (f32 scores for a stable softmax)
        s = jnp.einsum("nhqd,nhkd->nhqk", q, ck,
                       preferred_element_type=jnp.float32)
        s = s / math.sqrt(q.shape[-1])
        mask = jnp.arange(ck.shape[2]) <= self.pos
        s = jnp.where(mask[None, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
        o = jnp.einsum("nhqk,nhkd->nhqd", p, cv)
        return jnp.transpose(o, (0, 2, 1, 3))


class GPTDecoder:
    """Compiled KV-cache text generation over a (trained) `GPTModel`.

    Parameters are read from the model at construction, and again by
    `refresh()`, into the form every program reads them in:
    ``_params["layers"]`` is a tuple of one dict a layer whose leaves are
    buffers the decoder owns (the block's may be deleted once the decoder
    is built), float32 as the block holds them, each matrix the transposed
    copy ``(in, out)`` so that a product is ``x @ W``; ``embed`` and ``pos``
    (and ``head``, where the logits' matrix is not the tied embedding) are
    tables ``(n, C)`` with their rows padded to whole lanes (`_rows`): rows
    are gathered from them and the logits contract over their last axis;
    the final norm is the block's own buffers. No program slices,
    transposes or re-lays out a weight. The
    jit cache persists across calls, so repeated generation with the same
    shapes never recompiles; `generate` is unrolled over the layers, so
    its compile grows with the depth (`CHANGES.md`, PR 32).
    """

    def __init__(self, model):
        self._model = model
        self._n_heads = model.blocks[0].attn._num_heads
        self._units = model.blocks[0].attn._units
        self._tie = model._tie
        self._max_length = int(model.position_embed.shape[0])
        self._param_ids = None
        self._warned_stale = False
        self.refresh()

    # -- parameters ---------------------------------------------------------

    @staticmethod
    def _leaf(p):
        return p.data()._data  # noqa: SLF001 — jax value, zero-copy

    def _extract_params(self, model):
        layers = tuple(_stored(_own)({
            "ln1_g": self._leaf(blk.ln1.gamma),
            "ln1_b": self._leaf(blk.ln1.beta),
            "qkv_w": self._leaf(blk.attn.qkv.weight),
            "qkv_b": self._leaf(blk.attn.qkv.bias),
            "proj_w": self._leaf(blk.attn.proj.weight),
            "proj_b": self._leaf(blk.attn.proj.bias),
            "ln2_g": self._leaf(blk.ln2.gamma),
            "ln2_b": self._leaf(blk.ln2.beta),
            "ffn1_w": self._leaf(blk.ffn.ffn1.weight),
            "ffn1_b": self._leaf(blk.ffn.ffn1.bias),
            "ffn2_w": self._leaf(blk.ffn.ffn2.weight),
            "ffn2_b": self._leaf(blk.ffn.ffn2.bias),
        }) for blk in model.blocks)
        tables = {"embed": self._leaf(model.word_embed.weight),
                  "pos": self._leaf(model.position_embed)}
        if not self._tie:
            tables["head"] = self._leaf(model.lm_head.weight)
        return {
            "layers": layers,
            **_stored(_rows)(tables),
            "lnf_g": self._leaf(model.ln_f.gamma),
            "lnf_b": self._leaf(model.ln_f.beta),
        }

    def _current_ids(self):
        """Identity fingerprint of every live parameter buffer — jax
        arrays are immutable, so any set_data / optimizer step rebinds the
        buffer and changes its id."""
        return tuple(id(self._leaf(p)) for p in
                     self._model.collect_params().values())

    def refresh(self):
        """Re-read parameters from the model if any changed since the
        last read (cheap identity walk; the O(model) copy into the stored
        form only runs after an actual update)."""
        ids = self._current_ids()
        if ids != self._param_ids:
            self._params = self._extract_params(self._model)
            self._param_ids = ids

    # -- the mathematics (traced) --------------------------------------------

    def layer_kinds(self):
        """What each layer keeps in a slot (`serve/engine.py`): pages, all."""
        return ("pages",) * len(self._params["layers"])

    def kv_geometry(self):
        """``(layers, heads, head size, dtype)`` of the K/V rows a cache
        holds for this model."""
        layers = self._params["layers"]
        return (len(layers), self._n_heads,
                self._units // self._n_heads, layers[0]["qkv_w"].dtype)

    def embed(self, params, tokens, pos):
        """``tokens`` (N, T) at positions ``pos`` (N, T) or (T,), clamped to
        the position table: ``x`` (N, T, C). ``tokens`` (N,), one new row
        a sequence, at ``pos`` (N,): ``x`` (N, 1, C), the axis added after
        the gathers (gathered as ``(N, 1)`` the chip lays the rows out
        otherwise and a decode step of GPT-2 XL takes 0.11 ms more:
        `PERF.md` §6, PR 31)."""
        jnp = _j().numpy
        pos = jnp.clip(pos, 0, params["pos"].shape[0] - 1)
        x = params["embed"][tokens] + params["pos"][pos]
        x = x[..., :self._units]            # the tables' rows are whole lanes
        return x[:, None, :] if tokens.ndim == 1 else x

    def layer_params(self, params, li):
        """Layer `li`'s leaves, as they are stored."""
        return params["layers"][li]

    def layer(self, li, lp, x, pos, cache):  # noqa: ARG002
        """One pre-norm block over ``x`` (N, T, C): the family's ONE layer
        definition beside the Gluon block (`models/gpt.py`), which
        `tests/test_gpt.py` holds it to. `cache` is a cache-access object
        (`serve/pages.py`; `_PromptCache` / `_StepCache` below)::

            cache.attend(li, q, k, v) -> o      # (N, H, T, d) each

        stores the rows ``k, v`` of layer `li` and returns the attention of
        ``q`` over what it holds, rows in ``(N, T, H, d)`` order. `pos` is
        not read: the positions went in with `embed`."""
        jax = _j()
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        q, k, v = _split_qkv(_dense(h, lp["qkv_w"], lp["qkv_b"]),
                             self._n_heads)
        o = cache.attend(li, q, k, v)
        x = x + _dense(o.reshape(x.shape), lp["proj_w"], lp["proj_b"])
        h = _ln(x, lp["ln2_g"], lp["ln2_b"])
        ffn = _dense(jax.nn.gelu(_dense(h, lp["ffn1_w"], lp["ffn1_b"])),
                     lp["ffn2_w"], lp["ffn2_b"])
        return x + ffn

    def next_logits(self, params, x):
        """Next-token logits of residual rows ``x`` (..., C): the contraction
        over the last axis of the table as it is stored, ``(V, C)`` with its
        rows padded to whole lanes (``x`` is padded to match; the tied
        embedding, else ``head``). Row-major is how the chip keeps that
        table, so neither this nor `embed`'s gather re-lays it out."""
        jnp = _j().numpy
        w = params["embed" if self._tie else "head"]
        x = _ln(x, params["lnf_g"], params["lnf_b"])
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, w.shape[1] - x.shape[-1])])
        return jnp.einsum("...c,vc->...v", x, w)

    def _sample(self, logits, key, temperature, top_k, do_sample):
        jax = _j()
        jnp = jax.numpy
        if not do_sample:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits.astype(jnp.float32) / temperature
        if top_k is not None:
            vals, idx = jax.lax.top_k(logits, top_k)
            choice = jax.random.categorical(key, vals, axis=-1)
            return jnp.take_along_axis(
                idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    # -- the compiled program ----------------------------------------------

    @functools.cached_property
    def _generate_fn(self):
        jax = _j()
        jnp = jax.numpy
        lax = jax.lax

        def generate(params, tokens, t0, key, temperature, *, max_new,
                     top_k, do_sample, cache_len):
            # `tokens` is the BUCKET-padded prompt (N, B); `t0` is the
            # true prompt length, a traced scalar so every length in the
            # bucket shares one program. Padded positions write junk K/V
            # beyond t0, but decode overwrites position p before the
            # `arange <= pos` mask ever admits it, so the junk is never
            # attended.
            # Both halves run `layer`, the block the serving programs run,
            # a layer at a time over the per-layer leaves as those programs
            # do (one traced body a layer: the compile grows with the depth).
            # Each layer gets a dense cache of its own, so `li` is no index.
            B = tokens.shape[1]
            layers = params["layers"]

            # ---- prefill: full causal pass over the padded prompt ----
            x = self.embed(params, tokens, jnp.arange(B))
            ck, cv = [], []
            for lp in layers:
                cache = _PromptCache(cache_len)
                x = self.layer(None, lp, x, None, cache)
                ck.append(cache.k)
                cv.append(cache.v)
            # last REAL token (causal: its row never saw the padding)
            logits0 = self.next_logits(
                params, lax.dynamic_slice_in_dim(x, t0 - 1, 1,
                                                 axis=1)[:, 0])  # (N, V)

            # ---- decode: one scan step per new token ----
            def step(carry, step_key):
                ck, cv, pos, tok = carry

                x = self.embed(params, tok[:, None], pos[None])
                caches = [_StepCache(k, v, pos) for k, v in zip(ck, cv)]
                for lp, cache in zip(layers, caches):
                    x = self.layer(None, lp, x, None, cache)
                logits = self.next_logits(params, x[:, 0])
                nxt = self._sample(logits, step_key, temperature, top_k,
                                   do_sample)
                return (tuple(c.k for c in caches),
                        tuple(c.v for c in caches), pos + 1, nxt), tok

            first = self._sample(logits0, key, temperature, top_k,
                                 do_sample)
            # each step consumes the carried token and samples the next:
            # `first` + (max_new - 1) steps = max_new generated tokens
            keys = jax.random.split(jax.random.fold_in(key, 1),
                                    max_new)[1:]
            (_, _, _, last), toks = lax.scan(
                step, (tuple(ck), tuple(cv), t0.astype(jnp.int32), first),
                keys)
            # toks holds the CARRIED token per step; append the final
            # sample to complete max_new outputs
            out = jnp.concatenate(
                [jnp.transpose(toks, (1, 0)), last[:, None]], axis=1)
            return out

        from ..telemetry.compiles import ledgered_jit

        return ledgered_jit(generate,
                            family="gpt.generate",
                            static_argnames=("max_new", "top_k",
                                             "do_sample", "cache_len"))

    def _auto_refresh(self):
        """Re-read parameters if the model was updated since the last
        read. `refresh()` after a parameter update is easy to forget, so
        `generate` calls this on every entry (cheap identity walk): stale
        params are re-read automatically, with a one-time warning so the
        missing `refresh()` call gets fixed at the source."""
        ids = self._current_ids()
        if ids != self._param_ids:
            if self._param_ids is not None and not self._warned_stale:
                self._warned_stale = True
                _LOG.warning(
                    "GPTDecoder: model parameters changed since the last "
                    "refresh(); auto-refreshing. Call refresh() after "
                    "parameter updates to make the re-read explicit.")
            self.refresh()

    def generate(self, tokens, max_new_tokens, temperature=1.0, top_k=None,
                 do_sample=False, seed=None):
        """Generate `max_new_tokens` continuations of `tokens` (N, T0).

        Greedy by default; `do_sample=True` draws from the
        temperature-scaled (optionally top-k-truncated) distribution
        using the framework RNG (`mx.random.seed` reproduces runs).

        The prompt is padded to a :data:`PROMPT_BUCKETS` length bucket
        before compile, so ad-hoc prompt lengths share one XLA program
        per (batch, bucket, max_new) signature instead of one per exact
        length. Parameters are auto-refreshed if the model changed since
        the last read (see :meth:`_auto_refresh`).
        """
        jax = _j()
        jnp = jax.numpy
        from .. import random as mxrandom
        from ..ndarray.ndarray import NDArray

        self._auto_refresh()
        toks = tokens._data if isinstance(tokens, NDArray) else \
            jnp.asarray(tokens)
        toks = toks.astype(jnp.int32)
        if max_new_tokens <= 0:
            return NDArray(toks)          # no-op budget: prompt unchanged
        T0 = toks.shape[1]
        total = T0 + max_new_tokens
        if total > self._max_length:
            raise ValueError(
                f"prompt ({T0}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_length ({self._max_length})")
        padded, t0 = bucket_prompt(toks, max_len=self._max_length)
        if seed is not None:
            key = jax.random.PRNGKey(seed)
        else:
            key = mxrandom.next_key()
        new = self._generate_fn(
            self._params, padded, jnp.int32(t0), key,
            jnp.float32(max(temperature, 1e-6)),
            max_new=max_new_tokens,
            top_k=None if top_k is None else int(top_k),
            do_sample=bool(do_sample),
            cache_len=padded.shape[1] + max_new_tokens)
        return NDArray(jnp.concatenate([toks, new], axis=1))


class NgramProposer:
    """Model-free draft source for speculative decoding.

    Proposes the ``k`` tokens that followed the most recent earlier
    occurrence of the sequence's longest matching suffix n-gram —
    greedy decode of small models (and structured output in general)
    is highly repetitive, so a pure host-numpy suffix match drafts
    useful continuations with ZERO extra device programs. When nothing
    matches, it proposes a repeat of the last token (the cheapest
    guess that is still sometimes right for degenerate loops).

    The proposal is only ever a *hint*: the target model verifies every
    drafted token, so a bad draft costs acceptance rate, never
    correctness (see `serve.SlotDecoder` spec decode).
    """

    def __init__(self, k, max_ngram=3):
        self.k = int(k)
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.max_ngram = int(max_ngram)
        if self.max_ngram < 1:
            raise ValueError(f"max_ngram must be >= 1, got {max_ngram}")

    def propose(self, seq):
        """Draft ``k`` tokens continuing 1-D token id array ``seq``
        (prompt + everything generated so far). Returns ``(k,)`` int32
        host numpy."""
        seq = onp.asarray(seq, onp.int32).reshape(-1)
        if seq.size == 0:
            return onp.zeros(self.k, onp.int32)
        out = onp.full(self.k, seq[-1], onp.int32)     # fallback: repeat
        for n in range(min(self.max_ngram, seq.size - 1), 0, -1):
            pat = seq[-n:]
            # candidate windows strictly BEFORE the suffix itself
            wins = onp.lib.stride_tricks.sliding_window_view(seq, n)[:-1]
            hits = onp.flatnonzero((wins == pat).all(axis=1))
            if hits.size == 0:
                continue
            i = int(hits[-1])                          # most recent match
            cont = seq[i + n:i + n + self.k]
            if cont.size == 0:
                continue
            out[:cont.size] = cont
            out[cont.size:] = cont[-1]
            return out
        return out
