"""EvaByte: a byte-level decoder whose attention keeps one exact window and
chunk summaries of everything before it (EVA, arXiv:2302.04542, as the
release's ``config.json`` names it: ``attention_class: "eva"``,
``window_size``, ``chunk_size``).

The block is rotary, gated and bias-free: ``rms(x; g) = x / sqrt(mean(x^2) +
eps) * (1 + g)``; ``h = x + Attn(rms(x; g1))``; ``x' = h + W_down(silu(W_gate
u) * (W_up u))`` with ``u = rms(h; g2)``; the residual stream and the logits
stay float32 (``fp32_skip_add``, ``fp32_logits``), the matrices and the K/V
rows are held in `dtype` (bfloat16 as published). Position ``t`` in window
``w = t // window`` attends, in one softmax, the rows of its own window up to
itself and one summary row for every chunk of every window before:
``alpha = softmax_m(k_m . phi / sqrt(d))`` over a chunk's rotated keys,
``k^ = sum alpha_m k_m + mu``, ``v^ = sum alpha_m v_m`` (`summarize`). From the
moment a window is finished its exact rows are never read again.

This file is the family's ONE layer definition (`EvaByteDecoder.layer`): it
takes a cache-access object and knows no page, pool or table. `mx.serve`'s
prefill-chunk and decode programs (`serve/eva.py`) hand it theirs::

    cache.attend(li, q, k, v) -> o      # (T, H, d) each; q, k rotated

which stores the rows ``k, v`` of layer ``li`` and returns the attention of
``q`` over whatever the cache holds for those queries.

The output head has ``num_pred_heads * vocab`` rows; head ``p`` (rows ``p V
... (p+1) V - 1``) predicts byte ``t + 1 + p``. The served token is head 0's
(`next_logits`); the other heads' weights are held, and drafting several
bytes a step with them is not written (ROADMAP R7).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

__all__ = ["EvaByteConfig", "EvaByteDecoder", "rms", "rope", "summarize"]

#: the parameter tree: ``layers`` is a tuple of per-layer dicts (separate
#: leaves, so that no program slices a stacked array a layer at a time)
LAYER_LEAVES = ("g1", "wq", "wk", "wv", "wo", "phi", "mu", "g2", "w_gate",
                "w_up", "w_down")
TOP_LEAVES = ("embed", "norm", "head")
#: kept in the decoder's `dtype`; every other leaf stays float32
MATRICES = frozenset(("embed", "head", "wq", "wk", "wv", "wo", "w_gate",
                      "w_up", "w_down"))


@dataclass(frozen=True)
class EvaByteConfig:
    """Sizes under the names of EvaByte's ``config.json``."""

    num_hidden_layers: int = 32
    hidden_size: int = 4096
    num_attention_heads: int = 32
    intermediate_size: int = 11008
    vocab_size: int = 320
    num_pred_heads: int = 8
    window_size: int = 2048
    chunk_size: int = 16
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 32768

    @classmethod
    def from_dict(cls, cfg):
        return cls(**{k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg})

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def leaf_shapes(self):
        """``({top leaf: shape}, {layer leaf: shape})``; matrices are kept
        ``(in, out)``: a projection is ``x @ W``."""
        c, f = self.hidden_size, self.intermediate_size
        hd = (self.num_attention_heads, self.head_dim)
        top = {"embed": (self.vocab_size, c), "norm": (c,),
               "head": (c, self.num_pred_heads * self.vocab_size)}
        layer = {"g1": (c,), "wq": (c, c), "wk": (c, c), "wv": (c, c),
                 "wo": (c, c), "phi": hd, "mu": hd, "g2": (c,),
                 "w_gate": (c, f), "w_up": (c, f), "w_down": (f, c)}
        return top, layer


def rms(x, g, eps):
    """RMSNorm with unit offset, in float32."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) \
        * (1.0 + g.astype(jnp.float32))


def rope(x, pos, theta):
    """``x`` (T, H, d) float32 rotated at positions ``pos`` (T,): all of the
    head, half-split (first half with second half)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summarize(k, v, phi, mu):
    """Chunk summaries: ``k, v`` ``(..., chunk, d)`` rotated keys and values
    of whole chunks, ``phi, mu`` broadcastable ``(..., d)`` per head. Returns
    float32 ``(k^, v^)`` of shape ``(..., d)``."""
    import jax
    import jax.numpy as jnp

    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    a = jnp.sum(k * phi[..., None, :], axis=-1) / math.sqrt(k.shape[-1])
    a = jax.nn.softmax(a, axis=-1)[..., None]
    return jnp.sum(a * k, axis=-2) + mu, jnp.sum(a * v, axis=-2)


class EvaByteDecoder:
    """The family's weights, held once, and its layer.

    `params`: ``{"embed": (V, C), "norm": (C,), "head": (C, P V), "layers":
    [{leaf: array}, ...]}`` with the leaves of `EvaByteConfig.leaf_shapes`;
    matrices are cast to `dtype` (the arrays handed in are not kept), the
    norms' offsets and the per-head feature vectors stay float32."""

    family = "evabyte"

    def __init__(self, config, params, dtype="bfloat16"):
        import jax.numpy as jnp

        self.config = cfg = config
        self.dtype = jnp.dtype(dtype)
        top, layer = cfg.leaf_shapes()
        if len(params["layers"]) != cfg.num_hidden_layers:
            raise ValueError(
                f"{len(params['layers'])} layers given, the configuration "
                f"has {cfg.num_hidden_layers}")

        def take(name, a, shape, where=""):
            if tuple(a.shape) != tuple(shape):
                raise ValueError(f"{where}{name}: got {tuple(a.shape)}, the "
                                 f"configuration says {tuple(shape)}")
            return jnp.asarray(
                a, self.dtype if name in MATRICES else jnp.float32)

        self._params = {n: take(n, params[n], top[n]) for n in TOP_LEAVES}
        self._params["layers"] = tuple(
            {n: take(n, lp[n], layer[n], f"layers.{i}.") for n in LAYER_LEAVES}
            for i, lp in enumerate(params["layers"]))
        self._n_heads = cfg.num_attention_heads
        self._units = cfg.hidden_size
        self._max_length = cfg.max_position_embeddings

    def _auto_refresh(self):
        """The engine's hot-swap seam: these weights are the decoder's own
        and do not change under it."""

    def layer_kinds(self):
        """What each layer keeps in a slot (`serve/engine.py`): pages, all."""
        return ("pages",) * self.config.num_hidden_layers

    def kv_geometry(self):
        """``(layers, heads, head size, dtype)`` of the K/V rows a cache
        holds for this model."""
        cfg = self.config
        return (cfg.num_hidden_layers, cfg.num_attention_heads, cfg.head_dim,
                self.dtype)

    # -- the mathematics (traced) --------------------------------------------

    def embed(self, params, tokens, pos):  # noqa: ARG002
        """``tokens`` (N, T), N sequences of T new rows each, or (N,), one
        each: ``x`` (N T, C) float32, rows in that order (positions enter in
        `layer`, rotary)."""
        import jax.numpy as jnp

        return params["embed"][tokens.reshape(-1)].astype(jnp.float32)

    def layer_params(self, params, li):
        return params["layers"][li]

    def layer(self, li, lp, x, pos, cache):
        """One block: ``x`` (T, C) float32, ``pos`` the rows' positions (T
        of them, in any shape), `cache` as the module docstring says. Returns
        ``x'`` (T, C) float32."""
        import jax
        import jax.numpy as jnp

        cfg, dt = self.config, self.dtype
        pos = pos.reshape(-1)
        t = x.shape[0]
        hd = (t, cfg.num_attention_heads, cfg.head_dim)

        def mm(a, w):
            return jnp.matmul(a.astype(dt), w,
                              preferred_element_type=jnp.float32)

        u = rms(x, lp["g1"], cfg.rms_norm_eps)
        q = rope(mm(u, lp["wq"]).reshape(hd), pos, cfg.rope_theta)
        k = rope(mm(u, lp["wk"]).reshape(hd), pos, cfg.rope_theta)
        v = mm(u, lp["wv"]).reshape(hd)
        o = cache.attend(li, q, k, v)
        h = x + mm(o.reshape(t, cfg.hidden_size), lp["wo"])
        u = rms(h, lp["g2"], cfg.rms_norm_eps)
        return h + mm(jax.nn.silu(mm(u, lp["w_gate"])) * mm(u, lp["w_up"]),
                      lp["w_down"])

    def logits(self, params, x):
        """All prediction heads: ``(..., P V)`` float32."""
        import jax.numpy as jnp

        z = rms(x, params["norm"], self.config.rms_norm_eps)
        return jnp.matmul(z.astype(self.dtype), params["head"],
                          preferred_element_type=jnp.float32)

    def next_logits(self, params, x):
        """Head 0, the next byte's: what is served."""
        return self.logits(params, x)[..., :self.config.vocab_size]
