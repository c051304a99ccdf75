"""Plain reference of the openPangu-Ultra-MoE family: the forward pass in
straightforward ``jax.numpy``, float32 with ``highest`` matmul precision, no
cache, no pages, no kernel, no absorption, the experts a plain loop over the
held ids with masks. It imports nothing of the program.

The layer (sizes under the keys of the release's ``config.json``; a token's
residual ``x`` float32; every ``N_*`` an RMSNorm ``x / sqrt(mean(x^2) + eps) *
g`` with its own gain; no bias anywhere)::

    x = x + N_post_attn(Attn(N_in(x)))
    x = x + N_post_mlp(F(N_pre_mlp(x)))

``c_q = N_q(u W_qa)``; ``q_h = c_q W_qb,h = [q_nope_h ; q_rope_h]``;
``[c ; k_r] = u W_kva``, ``c_kv = N_kv(c)``, ``k_rope = RoPE(k_r)`` (one for
all heads); ``[k_nope_h ; v_h] = c_kv W_kvb,h``; score ``(q_nope_h . k_nope_h +
RoPE(q_rope_h) . k_rope) / sqrt(nope + rope)``; causal softmax; ``Attn =
concat_h(sum p v_h) W_o``. ``F`` of the first ``first_k_dense_replace`` layers
is a gated SiLU; of the others ``E_shared(u) + sum_e w_e E_e(u)`` with ``s =
sigmoid(u W_r)`` over all ``n_routed_experts``, the ``num_experts_per_tok``
largest chosen, ``w = routed_scaling_factor * s_chosen / (sum s_chosen +
1e-20)``, the sum taken over the chosen experts that are *held*
(``experts_held = [first, count]`` of the configuration file: one chip's share
of an expert-parallel group; what the absent experts would add is left out,
here and in the program alike). What is assumed beyond the source's keys is
listed in the configuration file under ``assumed``.

**It runs a part of a layer at a time over all checked requests.** Float32
copies of the 4.92 G parameters held are 19.7 GB and do not fit the chip; an
expert layer's are 4.0 GB. So a layer's attention weights are made from the
seed, every request goes through attention (a block of `Q_BLOCK` queries at a
time against the request's keys), the weights are dropped; then the router
and the shared expert; then the held experts one at a time (189 MB each),
every request through each under a mask.

`dtype="int8"` is the control: both inputs of every matmul rounded as
`chipbench/lib/lower.py` says, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import math

from chipbench.lib import lower, seeded

Q_BLOCK = 256

ATTN_LEAVES = (   # (name inside a layer, shape from sizes, kind)
    ("input_layernorm.weight", lambda s: (s.c,), "gain"),
    ("self_attn.q_a_proj.weight", lambda s: (s.rq, s.c), "weight"),
    ("self_attn.q_a_layernorm.weight", lambda s: (s.rq,), "gain"),
    ("self_attn.q_b_proj.weight",
     lambda s: (s.heads * (s.dn + s.dr), s.rq), "weight"),
    ("self_attn.kv_a_proj_with_mqa.weight",
     lambda s: (s.r + s.dr, s.c), "weight"),
    ("self_attn.kv_a_layernorm.weight", lambda s: (s.r,), "gain"),
    ("self_attn.kv_b_proj.weight",
     lambda s: (s.heads * (s.dn + s.dv), s.r), "weight"),
    ("self_attn.o_proj.weight", lambda s: (s.c, s.heads * s.dv), "weight"),
    ("post_attention_layernorm.weight", lambda s: (s.c,), "gain"),
    ("pre_mlp_layernorm.weight", lambda s: (s.c,), "gain"),
    ("post_mlp_layernorm.weight", lambda s: (s.c,), "gain"),
)
DENSE_LEAVES = (
    ("mlp.gate_proj.weight", lambda s: (s.f, s.c), "weight"),
    ("mlp.up_proj.weight", lambda s: (s.f, s.c), "weight"),
    ("mlp.down_proj.weight", lambda s: (s.c, s.f), "weight"),
)
SHARED_LEAVES = (
    ("mlp.gate.weight", lambda s: (s.experts, s.c), "weight"),
    ("mlp.shared_experts.gate_proj.weight", lambda s: (s.fs, s.c), "weight"),
    ("mlp.shared_experts.up_proj.weight", lambda s: (s.fs, s.c), "weight"),
    ("mlp.shared_experts.down_proj.weight", lambda s: (s.c, s.fs), "weight"),
)
#: one routed expert's leaves; expert `e` of layer `li` is seeded under
#: `expert_code(li, e)` in the place of the layer
EXPERT_LEAVES = (
    ("mlp.experts.gate_proj.weight", lambda s: (s.fm, s.c), "weight"),
    ("mlp.experts.up_proj.weight", lambda s: (s.fm, s.c), "weight"),
    ("mlp.experts.down_proj.weight", lambda s: (s.c, s.fm), "weight"),
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The sizes of a configuration file, under the release's own keys."""

    layers: int
    dense_layers: int
    c: int
    f: int
    fm: int
    heads: int
    rq: int
    r: int
    dn: int
    dr: int
    dv: int
    experts: int
    shared: int
    top_k: int
    route_scale: float
    vocab: int
    theta: float
    eps: float
    held: tuple
    init_std: float

    @property
    def fs(self):
        return self.fm * self.shared


def sizes(cfg):
    held = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    return Sizes(
        layers=cfg["num_hidden_layers"],
        dense_layers=min(cfg["first_k_dense_replace"],
                         cfg["num_hidden_layers"]),
        c=cfg["hidden_size"], f=cfg["intermediate_size"],
        fm=cfg["moe_intermediate_size"], heads=cfg["num_attention_heads"],
        rq=cfg["q_lora_rank"], r=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], experts=cfg["n_routed_experts"],
        shared=cfg["n_shared_experts"], top_k=cfg["num_experts_per_tok"],
        route_scale=float(cfg["routed_scaling_factor"]),
        vocab=cfg["vocab_size"], theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]), held=(int(held[0]), int(held[1])),
        init_std=float(cfg["init_std"]))


def expert_code(li, e):
    """Where `seeded.leaf` takes a layer: expert `e` of layer `li`."""
    return li + 1000 * (e + 1)


def top_leaves(cfg):
    s = sizes(cfg)
    return (("embed_tokens.weight", (s.vocab, s.c), "table"),
            ("norm.weight", (s.c,), "gain"),
            ("lm_head.weight", (s.vocab, s.c), "weight"))


def leaves(cfg):
    """``(name, tag, layer code, shape, kind)`` of every parameter held."""
    s = sizes(cfg)
    out = [(name, name, 0, shape, kind) for name, shape, kind in top_leaves(cfg)]
    for li in range(s.layers):
        group = ATTN_LEAVES + (DENSE_LEAVES if li < s.dense_layers
                               else SHARED_LEAVES)
        out += [(f"layers.{li}.{name}", name, li, shape(s), kind)
                for name, shape, kind in group]
        if li >= s.dense_layers:
            for e in held_ids(s):
                out += [(f"layers.{li}." + name.replace(
                    "experts.", f"experts.{e}."), name, expert_code(li, e),
                    shape(s), kind) for name, shape, kind in EXPERT_LEAVES]
    return out


def n_params(cfg):
    return sum(math.prod(shape) for _, _, _, shape, _ in leaves(cfg))


def leaf(key, tag, layer, shape, kind, init_std=None):
    """One seeded leaf in float32 (traceable): a matrix N(0, `init_std`)
    (the configuration's ``init_std``: 0.02 in the cell's file, 0.2 at the
    CPU tests' tiny widths; a gain takes none), a gain N(1, 0.02) —
    `chipbench/lib/seeded.py`'s own kinds — and the embedding
    table N(0, 1): a token's own row then weighs as much in the residual as
    what a normed sublayer adds to it, and the router's choice follows the
    token, not the context's mean (with a table of N(0, 0.02) every token of
    a context chooses the same experts)."""
    if kind == "gain":
        return seeded.leaf(key, tag, layer, shape, kind)
    z = seeded.leaf(key, tag, layer, shape, "weight") \
        / seeded.KINDS["weight"][1]
    return z if kind == "table" else z * init_std


# -- the parts of the layer (module-level, so that a control can leave one
# -- out: chipbench/control_pangu.py) ----------------------------------------

def norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def post_norm(x, g, eps):
    """The sandwich's second norm of a sublayer (`N_post_attn`,
    `N_post_mlp`)."""
    return norm(x, g, eps)


def rope(x, pos, theta):
    """``x`` (T, ..., d) rotated at positions ``pos`` (T,): half-split
    pairing (dimension ``i`` with ``i + d/2``), angle ``pos * theta^(-i /
    (d/2))``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]          # (T, half)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def scores(q_nope, k_nope, q_rope, k_rope, q=lambda x: x):
    """(H, T, R): the content term a head and the one rotary term."""
    import jax.numpy as jnp

    return jnp.einsum("thn,rhn->htr", q(q_nope), q(k_nope)) \
        + jnp.einsum("thd,rd->htr", q(q_rope), q(k_rope))


def route_weights(chosen, scale):
    import jax.numpy as jnp

    return scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def held_ids(s):
    """The routed experts whose part of the sum is computed."""
    return tuple(range(s.held[0], s.held[0] + s.held[1]))


def gated(u, w_gate, w_up, w_down, q=lambda x: x):
    """A gated SiLU with ``nn.Linear`` weights ``(out, in)``."""
    import jax

    g = jax.nn.silu(q(u) @ q(w_gate).T) * (q(u) @ q(w_up).T)
    return q(g) @ q(w_down).T


def shared_expert(p, u, q=lambda x: x):
    return gated(u, p["mlp.shared_experts.gate_proj.weight"],
                 p["mlp.shared_experts.up_proj.weight"],
                 p["mlp.shared_experts.down_proj.weight"], q)


def route(p, u, s):
    """``(ids (T, top_k), weights (T, top_k))``: sigmoid scores over every
    routed expert, never rounded (the control rounds matmul inputs of the
    layer's arithmetic, not the choice of experts)."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.sigmoid(u @ p["mlp.gate.weight"].T)
    chosen, ids = jax.lax.top_k(score, s.top_k)
    return ids.astype(jnp.int32), route_weights(chosen, s.route_scale)


def route_margin(p, u, s):
    """(T,) how decisively each token's choice settles which of the HELD
    experts it takes, in router logits: the least distance of a held
    expert's logit from the boundary it would have to cross (the 9th
    largest logit for a chosen expert, the 8th for one not chosen). Only a
    token with a small margin can choose another set of held experts under
    a rounding of the router's input."""
    import jax
    import jax.numpy as jnp

    z = u @ p["mlp.gate.weight"].T
    top = jax.lax.top_k(z, s.top_k + 1)[0]
    last_in, first_out = top[:, s.top_k - 1:s.top_k], top[:, s.top_k:]
    mine = z[:, s.held[0]:s.held[0] + s.held[1]]
    return jnp.min(jnp.where(mine >= last_in, mine - first_out,
                             last_in - mine), axis=-1)


def attention(p, x, s, q=lambda x: x):
    """``Attn(N_in(x))`` of one request, ``x`` (T, C), T a multiple of
    `Q_BLOCK`: a block of queries at a time against all T keys, causally."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    pos = jnp.arange(t)
    u = norm(x, p["input_layernorm.weight"], s.eps)
    c_q = norm(q(u) @ q(p["self_attn.q_a_proj.weight"]).T,
               p["self_attn.q_a_layernorm.weight"], s.eps)
    qry = (q(c_q) @ q(p["self_attn.q_b_proj.weight"]).T).reshape(
        t, s.heads, s.dn + s.dr)
    q_nope, q_rope = qry[..., :s.dn], rope(qry[..., s.dn:], pos, s.theta)
    kv = q(u) @ q(p["self_attn.kv_a_proj_with_mqa.weight"]).T
    c_kv = norm(kv[:, :s.r], p["self_attn.kv_a_layernorm.weight"], s.eps)
    k_rope = rope(kv[:, s.r:], pos, s.theta)
    kvb = (q(c_kv) @ q(p["self_attn.kv_b_proj.weight"]).T).reshape(
        t, s.heads, s.dn + s.dv)
    k_nope, v = kvb[..., :s.dn], kvb[..., s.dn:]

    def block(b):
        at = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = scores(q_nope[at], k_nope, q_rope[at], k_rope, q) \
            / math.sqrt(s.dn + s.dr)
        sc = jnp.where((pos[None, :] <= at[:, None])[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("htr,rhv->thv", q(pr), q(v))

    o = jax.lax.map(block, jnp.arange(t // Q_BLOCK)).reshape(
        t, s.heads * s.dv)
    return q(o) @ q(p["self_attn.o_proj.weight"]).T


@functools.lru_cache(maxsize=None)
def _programs(s, dtype):
    """The programs of one (sizes, dtype)."""
    import jax
    import jax.numpy as jnp

    q = lower.ROUND[dtype]      # "int8": every matmul's two inputs rounded
    hi = functools.partial(jax.default_matmul_precision, "highest")

    def weights(group):
        def make(key, code):
            return {name: leaf(key, name, code, shape(s), kind, s.init_std)
                    for name, shape, kind in group}
        return jax.jit(make)

    @jax.jit
    def embed(key, tokens):
        return leaf(key, "embed_tokens.weight", 0, (s.vocab, s.c),
                    "table")[tokens]

    @jax.jit
    def attn_step(p, x):
        """``h = x + N_post_attn(Attn(N_in(x)))`` and ``u = N_pre_mlp(h)``."""
        with hi():
            h = x + post_norm(attention(p, x, s, q),
                              p["post_attention_layernorm.weight"], s.eps)
            return h, norm(h, p["pre_mlp_layernorm.weight"], s.eps)

    @jax.jit
    def dense_step(p, u):
        with hi():
            return gated(u, p["mlp.gate_proj.weight"], p["mlp.up_proj.weight"],
                         p["mlp.down_proj.weight"], q)

    @jax.jit
    def route_step(p, u):
        """The choice of experts, and the shared expert's part of ``F``."""
        with hi():
            ids, w = route(p, u, s)
            return ids, w, shared_expert(p, u, q)

    @jax.jit
    def margin_step(p, u):
        with hi():
            return route_margin(p, u, s)

    @jax.jit
    def expert_step(p, u, ids, w, acc, e):
        """Expert `e` over every token, added under its weight where the
        token chose it (a mask; weight 0 elsewhere)."""
        with hi():
            mine = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
            y = gated(u, p["mlp.experts.gate_proj.weight"],
                      p["mlp.experts.up_proj.weight"],
                      p["mlp.experts.down_proj.weight"], q)
            return acc + mine[:, None] * y

    @jax.jit
    def finish(p, h, f):
        return h + post_norm(f, p["post_mlp_layernorm.weight"], s.eps)

    @jax.jit
    def head(key, rows):
        wh = leaf(key, "lm_head.weight", 0, (s.vocab, s.c), "weight",
                  s.init_std)
        with hi():
            z = norm(rows, leaf(key, "norm.weight", 0, (s.c,), "gain"), s.eps)
            return (q(z) @ q(wh).T).astype(jnp.float32)

    return dict(attn=weights(ATTN_LEAVES), dense=weights(DENSE_LEAVES),
                shared=weights(SHARED_LEAVES), expert=weights(EXPERT_LEAVES),
                embed=embed, attn_step=attn_step, dense_step=dense_step,
                route_step=route_step, margin_step=margin_step,
                expert_step=expert_step, finish=finish, head=head)


def forward(cfg, seed, tokens, lengths, dtype="float32", margins=None):
    """The last layer's residual rows of each request: a list of ``(T_b,
    C)`` device arrays, ``T_b`` request b's `lengths` rounded up to
    `Q_BLOCK` (`tokens` (B, T), right-padded; nothing after a position
    reaches it). A list given as `margins` gains, an expert layer, the
    requests' `route_margin` (host arrays ``(T_b,)``)."""
    import jax.numpy as jnp
    import numpy as onp

    s = sizes(cfg)
    tokens = onp.asarray(tokens, onp.int32)
    need = [-(-max(int(n), 1) // Q_BLOCK) * Q_BLOCK for n in lengths]
    if max(need) > tokens.shape[1]:
        tokens = onp.pad(tokens, ((0, 0), (0, max(need) - tokens.shape[1])))
    pr = _programs(s, dtype)
    key = seeded.key_of(seed)
    x = [pr["embed"](key, jnp.asarray(tokens[b, :n]))
         for b, n in enumerate(need)]
    for li in range(s.layers):
        p = pr["attn"](key, jnp.int32(li))
        u = [None] * len(x)
        for b in range(len(x)):
            x[b], u[b] = pr["attn_step"](p, x[b])
        norms = {"post_mlp_layernorm.weight": p["post_mlp_layernorm.weight"]}
        del p
        if li < s.dense_layers:
            p = pr["dense"](key, jnp.int32(li))
            f = [pr["dense_step"](p, ub) for ub in u]
        else:
            p = pr["shared"](key, jnp.int32(li))
            routed = [pr["route_step"](p, ub) for ub in u]
            if margins is not None:
                margins.append([onp.asarray(pr["margin_step"](p, ub))
                                for ub in u])
            f = [r[2] for r in routed]
            for e in held_ids(s):
                del p
                p = pr["expert"](key, jnp.int32(expert_code(li, e)))
                f = [pr["expert_step"](p, ub, r[0], r[1], fb, jnp.int32(e))
                     for ub, r, fb in zip(u, routed, f)]
        del p
        x = [pr["finish"](norms, xb, fb) for xb, fb in zip(x, f)]
    return x


def logits_at(cfg, seed, tokens, rows, dtype="float32", with_margin=False):
    """Logits of the reference at chosen positions.

    `tokens` is an int array (B, T), right-padded; `rows` lists ``(b, t)``
    pairs. Returns a float32 numpy array (len(rows), vocabulary). Each
    request is computed as far as the last of its rows. `with_margin`: also
    a (len(rows),) array, the least `route_margin` of the row's token over
    the expert layers (inf where there is none)."""
    import jax.numpy as jnp
    import numpy as onp

    s = sizes(cfg)
    rows = onp.asarray(rows, onp.int32).reshape(-1, 2)
    n_b = onp.asarray(tokens).shape[0]
    lengths = [0] * n_b
    for b, t in rows:
        lengths[b] = max(lengths[b], int(t) + 1)
    margins = [] if with_margin else None
    x = forward(cfg, seed, tokens, lengths, dtype, margins)
    head = _programs(s, dtype)["head"]
    key = seeded.key_of(seed)
    out = onp.zeros((len(rows), s.vocab), onp.float32)
    for b in range(n_b):
        mine = onp.flatnonzero(rows[:, 0] == b)
        # one compiled shape a request: its rows, padded to its length
        for lo in range(0, mine.size, x[b].shape[0]):
            part = mine[lo:lo + x[b].shape[0]]
            at = onp.zeros(x[b].shape[0], onp.int32)
            at[:part.size] = rows[part, 1]
            out[part] = onp.asarray(
                head(key, x[b][jnp.asarray(at)]))[:part.size]
    if not with_margin:
        return out
    least = onp.full(len(rows), onp.inf, onp.float32)
    for layer in margins:
        least = onp.minimum(least, onp.asarray(
            [layer[b][t] for b, t in rows], onp.float32))
    return out, least
