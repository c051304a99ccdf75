"""Plain reference of the EvaByte family: the forward pass in straightforward
``jax.numpy``, float32 with ``highest`` matmul precision, no cache, no pages.
It imports nothing of the program.

The layer (sizes under the keys of EvaByte's ``config.json``): residual stream
in float32; ``rms(x; g) = x / sqrt(mean(x^2) + eps) * (1 + g)``
(``norm_add_unit_offset``); ``h = x + Attn(rms(x; g1))``;
``x' = h + W_down(silu(W_gate u) * (W_up u))``, ``u = rms(h; g2)``; no bias.
``q, k`` are rotated by RoPE at their position (``rope_theta``, all of the
head, half-split). Attention is EVA's: position ``t`` in window
``w = t // window_size`` attends, in ONE softmax,

* the local set ``L = {m : m // window_size = w, m <= t}`` exactly, and
* the remote set ``R = {j : chunk j lies in a window < w}`` through one
  summary row a chunk of ``chunk_size`` positions:
  ``alpha = softmax_m(s * k_m . phi)`` over the chunk's rotated keys,
  ``k^_j = sum_m alpha_m k_m + mu``, ``v^_j = sum_m alpha_m v_m``
  (``phi`` = ``adaptive_phi``, ``mu`` = ``adaptive_mu_k``, per head).

The output head has ``num_pred_heads * vocab_size`` rows; head ``p`` is rows
``p V ... (p+1) V - 1`` and predicts byte ``t + 1 + p``. `logits_at` returns
head 0, the next byte: what the system serves. What is assumed beyond the
source's keys is listed in the configuration file under ``assumed``.

It is computed in blocks so that a row of 30 k bytes fits one chip: a layer's
weights are made once from the seed, then every request goes through the
layer a window of queries at a time. Each window's program builds the sets
`L` and `R` for its queries as masks over the window's own keys and over all
chunk summaries made so far (`window_attention`); `attention_by_sets` is the
same mathematics position by position with the sets as Python sets, kept for
the tests.

`dtype="int8"` is the control: both inputs of every matmul rounded as
`chipbench/lib/lower.py` says, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import math

from chipbench.lib import lower, seeded

# kind -> how a leaf is made from a standard normal z (see `leaf`)
LAYER_LEAVES = (  # (name inside a layer, shape from sizes, kind)
    ("input_layernorm.offset", lambda s: (s.c,), "offset"),
    ("self_attn.q_proj.weight", lambda s: (s.c, s.c), "matrix"),
    ("self_attn.k_proj.weight", lambda s: (s.c, s.c), "matrix"),
    ("self_attn.v_proj.weight", lambda s: (s.c, s.c), "matrix"),
    ("self_attn.o_proj.weight", lambda s: (s.c, s.c), "matrix"),
    ("self_attn.adaptive_phi", lambda s: (s.heads, s.d), "feature"),
    ("self_attn.adaptive_mu_k", lambda s: (s.heads, s.d), "feature"),
    ("post_attention_layernorm.offset", lambda s: (s.c,), "offset"),
    ("mlp.gate_proj.weight", lambda s: (s.f, s.c), "matrix"),
    ("mlp.up_proj.weight", lambda s: (s.f, s.c), "matrix"),
    ("mlp.down_proj.weight", lambda s: (s.c, s.f), "matrix"),
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The sizes of a configuration file, under EvaByte's own keys."""

    layers: int
    c: int
    heads: int
    f: int
    vocab: int
    pred: int
    window: int
    chunk: int
    theta: float
    eps: float
    init_std: float
    positions: int

    @property
    def d(self):
        return self.c // self.heads


def sizes(cfg):
    return Sizes(
        layers=cfg["num_hidden_layers"], c=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], f=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], pred=cfg["num_pred_heads"],
        window=cfg["window_size"], chunk=cfg["chunk_size"],
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        init_std=float(cfg["init_std"]),
        positions=cfg["max_position_embeddings"])


def top_leaves(cfg):
    s = sizes(cfg)
    return (("embed_tokens.weight", (s.vocab, s.c), "matrix"),
            ("norm.offset", (s.c,), "offset"),
            ("lm_head.weight", (s.pred * s.vocab, s.c), "matrix"))


def leaves(cfg):
    """``(name, tag, layer, shape, kind)`` of every parameter."""
    s = sizes(cfg)
    out = [(name, name, 0, shape, kind) for name, shape, kind in top_leaves(cfg)]
    for li in range(s.layers):
        out += [(f"layers.{li}.{name}", name, li, shape(s), kind)
                for name, shape, kind in LAYER_LEAVES]
    return out


def n_params(cfg):
    return sum(math.prod(shape) for _, _, _, shape, _ in leaves(cfg))


def leaf(key, tag, layer, shape, kind, init_std, d):
    """One seeded leaf in float32 (traceable): a matrix N(0, init_std), a
    norm's offset N(0, 0.02), a per-head feature vector (`adaptive_phi`,
    `adaptive_mu_k`) clip(N(0, 1), -1, 1) / sqrt(d), the release's
    initialiser. `seeded.leaf` gives N(0, 0.02); it is scaled from there."""
    import jax.numpy as jnp

    z = seeded.leaf(key, tag, layer, shape, "weight") / seeded.KINDS["weight"][1]
    if kind == "matrix":
        return z * init_std
    if kind == "offset":
        return z * 0.02
    return jnp.clip(z, -1.0, 1.0) / math.sqrt(d)


def rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def rope(x, pos, theta):
    """``x`` (T, H, d) rotated at positions ``pos`` (T,): half-split (the
    Llama convention), all of the head."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]          # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summaries(k, v, phi, mu, chunk, q=lambda x: x):
    """The chunk summaries of rotated keys ``k`` and values ``v`` (T, H, d),
    T a multiple of `chunk`: ``(T / chunk, H, d)`` each."""
    import jax
    import jax.numpy as jnp

    t, h, d = k.shape
    kc = k.reshape(t // chunk, chunk, h, d)
    vc = v.reshape(t // chunk, chunk, h, d)
    a = jnp.einsum("jmhd,hd->jmh", q(kc), q(phi)) / math.sqrt(d)
    a = jax.nn.softmax(a, axis=1)
    return (jnp.einsum("jmh,jmhd->jhd", q(a), q(kc)) + mu,
            jnp.einsum("jmh,jmhd->jhd", q(a), q(vc)))


def window_attention(qry, k, v, k_hat, v_hat, t0, window, chunk,
                     q=lambda x: x):
    """Attention of the queries at positions ``t0 ... t0 + T - 1`` (one
    window's, ``t0`` a multiple of `window`): ``k, v`` are that window's rows,
    ``k_hat, v_hat`` the summaries of every chunk of the sequence (those of
    later windows hold anything: they are outside `R`). (T, H, d)."""
    import jax
    import jax.numpy as jnp

    t, h, d = qry.shape
    pos = t0 + jnp.arange(t)
    w = pos // window
    # L: same window, not after t
    local = (pos[None, :] // window == w[:, None]) & (pos[None, :] <= pos[:, None])
    # R: chunks that lie in an earlier window
    first = jnp.arange(k_hat.shape[0]) * chunk
    remote = (first[None, :] + chunk - 1) // window < w[:, None]
    s_l = jnp.einsum("thd,mhd->htm", q(qry), q(k))
    s_r = jnp.einsum("thd,jhd->htj", q(qry), q(k_hat))
    s = jnp.concatenate([jnp.where(local[None], s_l, -jnp.inf),
                         jnp.where(remote[None], s_r, -jnp.inf)], -1)
    p = jax.nn.softmax(s / math.sqrt(d), axis=-1)
    return (jnp.einsum("htm,mhd->thd", q(p[..., :t]), q(v))
            + jnp.einsum("htj,jhd->thd", q(p[..., t:]), q(v_hat)))


def attention_by_sets(qry, k, v, phi, mu, window, chunk):
    """The same attention position by position, the sets `L` and `R` built
    as Python sets (numpy, float64; for the tests): ``(T, H, d)``."""
    import numpy as onp

    qry, k, v, phi, mu = (onp.asarray(a, onp.float64)
                          for a in (qry, k, v, phi, mu))
    t_all, h, d = qry.shape
    s = 1.0 / math.sqrt(d)
    out = onp.zeros_like(qry)
    for t in range(t_all):
        w = t // window
        local = {m for m in range(t_all) if m // window == w and m <= t}
        remote = {j for j in range(t_all // chunk)
                  if (j * chunk + chunk - 1) // window < w}
        for hd in range(h):
            keys, vals = [k[m, hd] for m in sorted(local)], \
                [v[m, hd] for m in sorted(local)]
            for j in sorted(remote):
                rows = range(j * chunk, (j + 1) * chunk)
                a = onp.array([s * k[m, hd] @ phi[hd] for m in rows])
                a = onp.exp(a - a.max())
                a /= a.sum()
                keys.append(sum(a[i] * k[m, hd] for i, m in enumerate(rows))
                            + mu[hd])
                vals.append(sum(a[i] * v[m, hd] for i, m in enumerate(rows)))
            e = onp.array([s * qry[t, hd] @ key for key in keys])
            e = onp.exp(e - e.max())
            out[t, hd] = sum(e[i] * vals[i] for i in range(len(vals))) / e.sum()
    return out


@functools.lru_cache(maxsize=None)
def _programs(s, dtype):
    """The programs of one (sizes, dtype): a layer's weights from the key,
    one window through a layer, embedding and head."""
    import jax
    import jax.numpy as jnp

    q = lower.ROUND[dtype]      # "int8": every matmul's two inputs rounded

    def w(key, tag, layer, shape, kind):
        return leaf(key, tag, layer, shape, kind, s.init_std, s.d)

    def weights(key, li):
        return {name: w(key, name, li, shape(s), kind)
                for name, shape, kind in LAYER_LEAVES}

    def embed(key, tokens):
        return w(key, "embed_tokens.weight", 0, (s.vocab, s.c), "matrix")[tokens]

    def window_step(p, x, t0, k_hat, v_hat):
        """One window of one request through one layer. ``k_hat, v_hat``
        (n_chunks, H, d) hold the summaries of the windows before; returns
        them with this window's written in."""
        t = x.shape[0]
        pos = t0 + jnp.arange(t)
        with jax.default_matmul_precision("highest"):
            u = rms(x, p["input_layernorm.offset"], s.eps)
            proj = lambda name: (q(u) @ q(p[name]).T).reshape(  # noqa: E731
                t, s.heads, s.d)
            qry = rope(proj("self_attn.q_proj.weight"), pos, s.theta)
            k = rope(proj("self_attn.k_proj.weight"), pos, s.theta)
            v = proj("self_attn.v_proj.weight")
            o = window_attention(qry, k, v, k_hat, v_hat, t0, s.window,
                                 s.chunk, q)
            h = x + q(o.reshape(t, s.c)) @ q(p["self_attn.o_proj.weight"]).T
            u = rms(h, p["post_attention_layernorm.offset"], s.eps)
            g = jax.nn.silu(q(u) @ q(p["mlp.gate_proj.weight"]).T) \
                * (q(u) @ q(p["mlp.up_proj.weight"]).T)
            out = h + q(g) @ q(p["mlp.down_proj.weight"]).T
            kh, vh = summaries(k, v, p["self_attn.adaptive_phi"],
                               p["self_attn.adaptive_mu_k"], s.chunk, q)
        at = t0 // s.chunk
        return (out, jax.lax.dynamic_update_slice_in_dim(k_hat, kh, at, 0),
                jax.lax.dynamic_update_slice_in_dim(v_hat, vh, at, 0))

    def head(key, rows):
        wh = w(key, "lm_head.weight", 0, (s.pred * s.vocab, s.c), "matrix")
        with jax.default_matmul_precision("highest"):
            z = rms(rows, w(key, "norm.offset", 0, (s.c,), "offset"), s.eps)
            return (q(z) @ q(wh[:s.vocab]).T).astype(jnp.float32)

    return (jax.jit(weights), jax.jit(embed), jax.jit(window_step),
            jax.jit(head))


def logits_at(cfg, seed, tokens, rows, dtype="float32"):
    """Head-0 logits of the reference at chosen positions.

    `tokens` is an int array (B, T), right-padded (nothing after a position
    reaches it); `rows` lists ``(b, t)`` pairs. Returns a float32 numpy array
    (len(rows), vocabulary). Each request is computed as far as the last
    window that holds one of its rows."""
    import jax.numpy as jnp
    import numpy as onp

    s = sizes(cfg)
    tokens = onp.asarray(tokens, onp.int32)
    rows = onp.asarray(rows, onp.int32).reshape(-1, 2)
    n_b, t_pad = tokens.shape
    win = s.window
    n_win = [0] * n_b
    for b, t in rows:
        n_win[b] = max(n_win[b], int(t) // win + 1)
    need = max(n_win) * win
    if need > t_pad:
        tokens = onp.pad(tokens, ((0, 0), (0, need - t_pad)))
    # every chunk the configuration's positions hold: one compiled shape
    n_chunks = max(s.positions, need) // s.chunk
    weights, embed, window_step, head = _programs(s, dtype)
    key = seeded.key_of(seed)
    # x[b][w]: window w of request b, (window, C)
    x = [[embed(key, jnp.asarray(tokens[b, w * win:(w + 1) * win]))
          for w in range(n_win[b])] for b in range(n_b)]
    for li in range(s.layers):
        p = weights(key, jnp.int32(li))
        for b in range(n_b):
            k_hat = jnp.zeros((n_chunks, s.heads, s.d), jnp.float32)
            v_hat = jnp.zeros_like(k_hat)
            for w in range(n_win[b]):
                x[b][w], k_hat, v_hat = window_step(
                    p, x[b][w], jnp.int32(w * win), k_hat, v_hat)
        del p
    out = onp.zeros((len(rows), s.vocab), onp.float32)
    for b in range(n_b):
        mine = onp.flatnonzero(rows[:, 0] == b)
        if mine.size:
            at = jnp.asarray(rows[mine, 1])
            out[mine] = onp.asarray(head(key, jnp.concatenate(x[b])[at]))
    return out
