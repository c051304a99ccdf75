"""Plain reference of the GPT-2 family: the published forward pass in
straightforward ``jax.numpy``, float32 with ``highest`` matmul precision, no
cache, no paging, no batching tricks. It imports nothing of the program.

Published description followed (Radford et al. 2019, and the `config.json` of
`openai-community/gpt2-xl`): token + learned position embeddings, `n_layer`
pre-norm blocks (LN -> fused qkv projection -> causal softmax attention over
`n_head` heads -> output projection -> residual; LN -> FFN with the tanh GELU
``gelu_new`` -> residual), a final LN, and the output head tied to the token
embedding. The fused projection's output features are ordered (q|k|v) x head x
head_dim, as GPT-2's ``c_attn`` is. No departure.

`dtype="int8"` is the control: float32 throughout, both inputs of every
matmul rounded as `chipbench/lib/lower.py` says, the step below the bfloat16
products that the configuration states and the served model multiplies with.

Weights are asked from `chipbench.lib.seeded` by name, one layer at a time and
inside the layer's own program, so the whole model is never resident.
"""
from __future__ import annotations

import functools
import math

from chipbench.lib import lower, seeded

LAYER_LEAVES = (  # (name inside a block, shape as a function of sizes, kind)
    ("ln1.gamma", lambda c, f: (c,), "gain"),
    ("ln1.beta", lambda c, f: (c,), "bias"),
    ("attn.qkv.weight", lambda c, f: (3 * c, c), "weight"),
    ("attn.qkv.bias", lambda c, f: (3 * c,), "bias"),
    ("attn.proj.weight", lambda c, f: (c, c), "weight"),
    ("attn.proj.bias", lambda c, f: (c,), "bias"),
    ("ln2.gamma", lambda c, f: (c,), "gain"),
    ("ln2.beta", lambda c, f: (c,), "bias"),
    ("ffn.ffn1.weight", lambda c, f: (f, c), "weight"),
    ("ffn.ffn1.bias", lambda c, f: (f,), "bias"),
    ("ffn.ffn2.weight", lambda c, f: (c, f), "weight"),
    ("ffn.ffn2.bias", lambda c, f: (c,), "bias"),
)


def sizes(cfg):
    """(layers, width, heads, ffn width, vocabulary, positions) of a config
    file, under the keys GPT-2's own ``config.json`` uses."""
    c = cfg["n_embd"]
    return (cfg["n_layer"], c, cfg["n_head"], cfg.get("n_inner") or 4 * c,
            cfg["vocab_size"], cfg["n_positions"])


def top_leaves(cfg):
    _, c, _, _, v, p = sizes(cfg)
    return (("word_embed.weight", (v, c), "weight"),
            ("position_embed", (p, c), "weight"),
            ("ln_f.gamma", (c,), "gain"), ("ln_f.beta", (c,), "bias"))


def leaves(cfg):
    """``(gluon name, tag, layer, shape, kind)`` of every parameter: the list
    `seeded.fill` gives the program's block, and this file reads again."""
    n_layer, c, _, f, _, _ = sizes(cfg)
    out = [(name, name, 0, shape, kind) for name, shape, kind in top_leaves(cfg)]
    for li in range(n_layer):
        out += [(f"blocks.{li}.{name}", name, li, shape(c, f), kind)
                for name, shape, kind in LAYER_LEAVES]
    return out


def n_params(cfg):
    return sum(math.prod(shape) for _, _, _, shape, _ in leaves(cfg))


def _ln(x, g, b, eps):
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _programs(dims, eps, dtype):
    """The three programs of one (sizes, dtype): embed, one block, the head.
    Each makes its own weights from the key, so no weight is an argument."""
    import jax
    import jax.numpy as jnp

    n_layer, c, n_head, f, v, n_pos = dims
    d = c // n_head
    # float32 on a TPU multiplies in bf16 passes unless told otherwise
    dt, prec = jnp.float32, "highest"
    q = lower.ROUND[dtype]      # "int8": every matmul's two inputs rounded

    def w(key, tag, layer, shape, kind):
        return seeded.leaf(key, tag, layer, shape, kind).astype(dt)

    def embed(key, tokens):
        we = w(key, "word_embed.weight", 0, (v, c), "weight")
        pe = w(key, "position_embed", 0, (n_pos, c), "weight")
        return we[tokens] + pe[:tokens.shape[1]][None]

    def block(key, li, x):
        p = {name: w(key, name, li, shape(c, f), kind)
             for name, shape, kind in LAYER_LEAVES}
        n, t, _ = x.shape
        with jax.default_matmul_precision(prec):
            h = _ln(x, p["ln1.gamma"], p["ln1.beta"], eps)
            qkv = q(h) @ q(p["attn.qkv.weight"]).T + p["attn.qkv.bias"]
            qry, k, val = (qkv.reshape(n, t, 3, n_head, d)[:, :, i]
                           for i in range(3))
            s = jnp.einsum("nqhd,nkhd->nhqk", q(qry), q(k),
                           preferred_element_type=jnp.float32) / math.sqrt(d)
            causal = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("nhqk,nkhd->nqhd", q(a), q(val)).reshape(n, t, c)
            x = x + (q(o) @ q(p["attn.proj.weight"]).T + p["attn.proj.bias"])
            h = _ln(x, p["ln2.gamma"], p["ln2.beta"], eps)
            h = jax.nn.gelu(q(h) @ q(p["ffn.ffn1.weight"]).T
                            + p["ffn.ffn1.bias"], approximate=True)
            return x + (q(h) @ q(p["ffn.ffn2.weight"]).T + p["ffn.ffn2.bias"])

    def head(key, rows):
        we = w(key, "word_embed.weight", 0, (v, c), "weight")
        with jax.default_matmul_precision(prec):
            h = _ln(rows, w(key, "ln_f.gamma", 0, (c,), "gain"),
                    w(key, "ln_f.beta", 0, (c,), "bias"), eps)
            return (q(h) @ q(we).T).astype(jnp.float32)

    return jax.jit(embed), jax.jit(block), jax.jit(head)


def logits_at(cfg, seed, tokens, rows, dtype="float32"):
    """Logits of the reference at chosen positions.

    `tokens` is an int array (B, T), right-padded (causality keeps padding
    out of every earlier position); `rows` lists ``(b, t)`` pairs. Returns a
    float32 numpy array (len(rows), vocabulary)."""
    import jax.numpy as jnp
    import numpy as onp

    dims = sizes(cfg)
    embed, block, head = _programs(dims, float(cfg["layer_norm_epsilon"]),
                                   dtype)
    key = seeded.key_of(seed)
    x = embed(key, jnp.asarray(tokens, jnp.int32))
    for li in range(dims[0]):
        x = block(key, jnp.int32(li), x)
    rows = onp.asarray(rows, onp.int32).reshape(-1, 2)
    picked = x[rows[:, 0], rows[:, 1]]
    return onp.asarray(head(key, picked))
