"""Plain reference of BERT-base masked-language-model training: forward, loss,
gradients and the Adam update in straightforward ``jax.numpy``, float32 with
``highest`` matmul precision, no kernels, no mixed precision. It imports
nothing of the program.

Published description followed (Devlin et al. 2019, `config.json` of
`google-bert/bert-base-uncased`): token + learned position embeddings -> LN;
`num_hidden_layers` post-norm blocks (fused qkv projection ordered (q|k|v) x
head x head_dim -> softmax attention -> output projection -> LN(x + .) -> FFN
with the erf GELU -> LN(x + .)); MLM head dense -> LN -> vocabulary decoder;
mean cross-entropy. Departures, each because the program's
`models.bert.BERTModel` behind `DataParallel.step(x, y)` computes it so and the
two must compute the same function:

* no segment (token-type) embedding is added: the step feeds token ids alone;
  that table and the next-sentence head get no gradient and are left out of
  the comparison by the rule on the reference's gradient;
* the MLM head's dense layer uses tanh (published: GELU) and its decoder has
  a weight of its own (published: tied to the token embedding);
* the loss is taken at every position against the label given (packed
  documents, no padding), not only at masked positions;
* the optimizer is MXNet's Adam: ``lr_t = lr sqrt(1-b2^t)/(1-b1^t)``,
  ``w -= lr_t m / (sqrt(v) + eps)``.

`matmul="int8"` is the control, the step that would tempt a later PR below
bfloat16: every matmul's two inputs are rounded as
`chipbench/lib/lower.py` says (straight-through gradient either way).
"""
from __future__ import annotations

import functools
import math

from chipbench.lib import lower, seeded

LAYER_LEAVES = (
    ("attention.qkv.weight", lambda c, f: (3 * c, c), "weight"),
    ("attention.qkv.bias", lambda c, f: (3 * c,), "bias"),
    ("attention.proj.weight", lambda c, f: (c, c), "weight"),
    ("attention.proj.bias", lambda c, f: (c,), "bias"),
    ("ffn.ffn1.weight", lambda c, f: (f, c), "weight"),
    ("ffn.ffn1.bias", lambda c, f: (f,), "bias"),
    ("ffn.ffn2.weight", lambda c, f: (c, f), "weight"),
    ("ffn.ffn2.bias", lambda c, f: (c,), "bias"),
    ("ln1.gamma", lambda c, f: (c,), "gain"),
    ("ln1.beta", lambda c, f: (c,), "bias"),
    ("ln2.gamma", lambda c, f: (c,), "gain"),
    ("ln2.beta", lambda c, f: (c,), "bias"),
)


def sizes(cfg):
    """(layers, width, heads, ffn width, vocabulary, positions, type vocab)."""
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["intermediate_size"],
            cfg["vocab_size"], cfg["max_position_embeddings"],
            cfg["type_vocab_size"])


def top_leaves(cfg):
    _, c, _, _, v, p, tv = sizes(cfg)
    return (("encoder.position_embed", (p, c), "weight"),
            ("encoder.word_embed.weight", (v, c), "weight"),
            ("encoder.token_type_embed.weight", (tv, c), "weight"),
            ("encoder.ln.gamma", (c,), "gain"),
            ("encoder.ln.beta", (c,), "bias"),
            ("mlm_dense.weight", (c, c), "weight"),
            ("mlm_dense.bias", (c,), "bias"),
            ("mlm_ln.gamma", (c,), "gain"), ("mlm_ln.beta", (c,), "bias"),
            ("mlm_decoder.weight", (v, c), "weight"),
            ("mlm_decoder.bias", (v,), "bias"),
            ("nsp.weight", (2, c), "weight"), ("nsp.bias", (2,), "bias"))


def leaves(cfg):
    """``(gluon name, tag, layer, shape, kind)`` of every parameter."""
    n_layer, c, _, f = sizes(cfg)[:4]
    out = [(name, name, 0, shape, kind) for name, shape, kind in top_leaves(cfg)]
    for li in range(n_layer):
        out += [(f"encoder.layers.{li}.{name}", name, li, shape(c, f), kind)
                for name, shape, kind in LAYER_LEAVES]
    return out


def n_params(cfg):
    return sum(math.prod(shape) for _, _, _, shape, _ in leaves(cfg))


def _ln(x, g, b, eps):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _loss_fn(cfg, matmul):
    """``loss(params, tokens, labels)`` over one block of rows; `params` maps
    the Gluon names to arrays."""
    import jax
    import jax.numpy as jnp

    n_layer, c, n_head = sizes(cfg)[:3]
    d = c // n_head
    eps = float(cfg["layer_norm_eps"])
    q8 = lower.ROUND[matmul]

    def mm(x, w):                       # x @ w.T, as a Dense layer does
        return q8(x) @ q8(w).T

    def loss(params, tokens, labels):
        g = params.__getitem__
        n, t = tokens.shape
        x = g("encoder.word_embed.weight")[tokens] \
            + g("encoder.position_embed")[:t][None]
        x = _ln(x, g("encoder.ln.gamma"), g("encoder.ln.beta"), eps)

        @jax.checkpoint
        def block(x, p):
            qkv = mm(x, p["attention.qkv.weight"]) + p["attention.qkv.bias"]
            q, k, v = (qkv[..., i * c:(i + 1) * c].reshape(n, t, n_head, d)
                       for i in range(3))
            s = jnp.einsum("nqhd,nkhd->nhqk", q8(q), q8(k)) / math.sqrt(d)
            a = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("nhqk,nkhd->nqhd", q8(a), q8(v)).reshape(n, t, c)
            h = mm(o, p["attention.proj.weight"]) + p["attention.proj.bias"]
            x = _ln(x + h, p["ln1.gamma"], p["ln1.beta"], eps)
            h = jax.nn.gelu(mm(x, p["ffn.ffn1.weight"]) + p["ffn.ffn1.bias"],
                            approximate=False)
            h = mm(h, p["ffn.ffn2.weight"]) + p["ffn.ffn2.bias"]
            return _ln(x + h, p["ln2.gamma"], p["ln2.beta"], eps)

        for li in range(n_layer):
            x = block(x, {name: g(f"encoder.layers.{li}.{name}")
                          for name, _, _ in LAYER_LEAVES})
        h = jnp.tanh(mm(x, g("mlm_dense.weight")) + g("mlm_dense.bias"))
        h = _ln(h, g("mlm_ln.gamma"), g("mlm_ln.beta"), eps)
        scores = mm(h, g("mlm_decoder.weight")) + g("mlm_decoder.bias")
        logp = jax.nn.log_softmax(scores, axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return -picked.mean()

    return loss


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items, matmul):
    import jax

    loss = _loss_fn(dict(cfg_items), matmul)

    def run(params, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss)(params, tokens, labels)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _adam_program():
    import jax
    import jax.numpy as jnp

    def run(params, grads, m, v, t, lr, b1, b2, eps):
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        params = jax.tree.map(
            lambda w, a, b: w - lr_t * a / (jnp.sqrt(b) + eps), params, m, v)
        return params, m, v

    return jax.jit(run, donate_argnums=(0, 2, 3))


@functools.lru_cache(maxsize=None)
def _norms_program():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda tree: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree))


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def loss_and_grads(cfg, params, tokens, labels, block_rows, matmul="float32",
                   devices=None):
    """Mean loss and its gradient over all rows, taken `block_rows` rows at a
    time (equal blocks, so the mean of the blocks' means is the mean). With
    several `devices` the blocks go round them in turn, each holding a copy
    of `params` and summing its own blocks; the sums meet on the first."""
    import jax
    import jax.numpy as jnp

    n = tokens.shape[0]
    if n % block_rows:
        raise ValueError(f"{n} rows do not divide into blocks of {block_rows}")
    run = _grad_program(_hashable(cfg), matmul)
    devices = list(devices or [None])
    copies = [params if d is None or i == 0 else jax.device_put(params, d)
              for i, d in enumerate(devices)]
    sums = [None] * len(devices)
    for k, r in enumerate(range(0, n, block_rows)):
        i = k % len(devices)
        put = (lambda a: jax.device_put(a, devices[i])) if devices[i] \
            is not None else jnp.asarray
        got = run(copies[i], put(tokens[r:r + block_rows].astype("int32")),
                  put(labels[r:r + block_rows].astype("int32")))
        sums[i] = got if sums[i] is None else jax.tree.map(jnp.add, sums[i], got)
    home = devices[0]
    total = sums[0]
    for part in sums[1:]:
        if part is not None:
            total = jax.tree.map(jnp.add, total, jax.device_put(part, home))
    k = n // block_rows
    loss, grads = total
    return loss / k, jax.tree.map(lambda a: a / k, grads)


def train_readings(cfg, seed, batches, opt, block_rows=8, matmul="float32",
                   rows=None, devices=None):
    """Follow the first ``len(batches)`` steps from the seed's weights.

    Returns ``{"loss": [per step], "grad": {leaf: the first gradient, on the
    host}, "grad_norm": {leaf: its norm}, "delta_norm": {leaf: norm of the
    change after all steps}}``. `opt` holds Adam's ``learning_rate, beta1, beta2,
    epsilon``. `rows`, a slice, plants the fault of a step that leaves part
    of the batch out and takes the mean over the rest. `devices`: the chips
    the blocks of rows are spread over (a four-chip cell's reference would
    otherwise take four times a one-chip cell's, on one chip of four)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    params = seeded.values(leaves(cfg), seed)
    first = jax.tree.map(jnp.copy, params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    norms = _norms_program()
    out = {"loss": [], "grad_norm": None, "delta_norm": None}
    for t, (tokens, labels) in enumerate(batches, start=1):
        if rows is not None:
            tokens, labels = tokens[rows], labels[rows]
        loss, grads = loss_and_grads(cfg, params, tokens, labels, block_rows,
                                     matmul, devices)
        out["loss"].append(float(loss))
        if t == 1:
            out["grad_norm"] = {k: float(x) for k, x in norms(grads).items()}
            out["grad"] = {k: onp.asarray(x) for k, x in grads.items()}
        params, m, v = _adam_program()(
            params, grads, m, v, jnp.float32(t),
            jnp.float32(opt["learning_rate"]), jnp.float32(opt["beta1"]),
            jnp.float32(opt["beta2"]), jnp.float32(opt["epsilon"]))
    delta = jax.tree.map(jnp.subtract, params, first)
    out["delta_norm"] = {k: float(x) for k, x in norms(delta).items()}
    return out
