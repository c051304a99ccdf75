"""Plain reference of the Nemotron-H family (``model_type`` ``nemotron_h``):
the forward pass in straightforward ``jax.numpy``, float32 with ``highest``
matmul precision, no cache, no pages, no kernel, no chunked scan, the experts
a plain loop over the held ids with masks. It imports nothing of the program.

Every block is ONE mixer behind one RMSNorm (sizes under the keys of the
release's ``config.json``; a token's residual ``x`` float32; ``N(x) = x /
sqrt(mean(x^2) + eps) * g``; ``nn.Linear`` weights ``(out, in)``, no bias)::

    x = x + Mixer_l(N_l(x))

the kind of mixer a block given by ``hybrid_override_pattern``; after the last
block a final norm and the untied head. No positional term anywhere.

``M``, Mamba-2: ``[z ; xBC ; dt] = u W_in``; ``xBC_t = silu(sum_j w_j *
xBC_{t-K+1+j} + b)`` (causal depthwise over ``conv_kernel`` rows, zeros before
the sequence); split ``x (H, P)``, ``B (G, N)``, ``C (G, N)``; ``Delta =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h`` of group ``h //
(H / G)`` the SEQUENTIAL recurrence, a `lax.scan` over tokens::

    S_t = exp(Delta A) S_{t-1} + Delta x_t (x) B_t          S_{-1} = 0
    y_t = S_t C_t + D x_t

``y = GroupRMSNorm_G(y * silu(z)) * g`` (gate first, then a norm over each
group's values); ``out = y W_out``. ``*``, attention: ``num_attention_heads``
query heads over ``num_key_value_heads`` stored heads (query head ``h`` reads
stored head ``h // rep``), scale ``1 / sqrt(head_dim)``, one masked softmax,
no rotary. ``E``, the latent expert layer: ``s = sigmoid(u W_r)`` over all
``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s + b`` chosen
(``b`` the score-correction bias: choice only); ``w = routed_scaling_factor *
s_chosen / (sum s_chosen + 1e-20)``; ``v = u W_dn``; ``E_e(v) = relu(v
W1_e)^2 W2_e``; ``out = (sum_e w_e E_e(v)) W_up + relu(u S1)^2 S2``, the sum
taken over the chosen experts that are *held* (``experts_held = [first,
count]`` of the configuration file: one chip's share of an expert-parallel
group; what the absent experts would add is left out, here and in the program
alike). What is assumed beyond the source's keys is listed in the
configuration file under ``assumed``.

**It runs a block at a time over all checked requests**, the block's weights
made from the seed and dropped after it; attention a block of `Q_BLOCK`
queries at a time against the request's keys; the held experts one at a time,
every request through each under a mask.

`dtype="int8"` is the control: both inputs of every matmul rounded as
`chipbench/lib/lower.py` says, the step below the configuration's bfloat16.
The module-level `carry` (what the scan keeps of the state between tokens)
and `forward`'s `initial` (the state a request starts from) are where a
control plants the two faults of a recurrent state: one kept in a lower
precision, one not reset.
"""
from __future__ import annotations

import dataclasses
import functools
import math

from chipbench.lib import lower, seeded

Q_BLOCK = 256

MAMBA_LEAVES = (   # (name inside a block, shape from sizes, kind)
    ("norm.weight", lambda s: (s.c,), "gain"),
    ("mixer.in_proj.weight", lambda s: (s.di + s.cc + s.h, s.c), "weight"),
    ("mixer.conv1d.weight", lambda s: (s.cc, s.k), "conv"),
    ("mixer.conv1d.bias", lambda s: (s.cc,), "bias"),
    ("mixer.dt_bias", lambda s: (s.h,), "dt_bias"),
    ("mixer.A_log", lambda s: (s.h,), "a_log"),
    ("mixer.D", lambda s: (s.h,), "gain"),
    ("mixer.norm.weight", lambda s: (s.di,), "gain"),
    ("mixer.out_proj.weight", lambda s: (s.c, s.di), "weight"),
)
ATTN_LEAVES = (
    ("norm.weight", lambda s: (s.c,), "gain"),
    ("mixer.q_proj.weight", lambda s: (s.hq * s.d, s.c), "weight"),
    ("mixer.k_proj.weight", lambda s: (s.hk * s.d, s.c), "weight"),
    ("mixer.v_proj.weight", lambda s: (s.hk * s.d, s.c), "weight"),
    ("mixer.o_proj.weight", lambda s: (s.c, s.hq * s.d), "weight"),
)
SHARED_LEAVES = (
    ("norm.weight", lambda s: (s.c,), "gain"),
    ("mixer.gate.weight", lambda s: (s.experts, s.c), "weight"),
    ("mixer.gate.e_score_correction_bias", lambda s: (s.experts,), "bias"),
    ("mixer.fc1_latent_proj.weight", lambda s: (s.lat, s.c), "weight"),
    ("mixer.fc2_latent_proj.weight", lambda s: (s.c, s.lat), "weight"),
    ("mixer.shared_experts.up_proj.weight", lambda s: (s.fs, s.c), "weight"),
    ("mixer.shared_experts.down_proj.weight", lambda s: (s.c, s.fs),
     "weight"),
)
#: one routed expert's leaves; expert `e` of block `li` is seeded under
#: `expert_code(li, e)` in the place of the layer
EXPERT_LEAVES = (
    ("mixer.experts.up_proj.weight", lambda s: (s.f, s.lat), "weight"),
    ("mixer.experts.down_proj.weight", lambda s: (s.lat, s.f), "weight"),
)
GROUPS = {"M": MAMBA_LEAVES, "*": ATTN_LEAVES, "E": SHARED_LEAVES}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The sizes of a configuration file, under the release's own keys."""

    pattern: str
    c: int
    h: int          # Mamba heads
    p: int          # their size
    g: int          # groups sharing B and C
    n: int          # state size
    k: int          # convolution rows
    hq: int
    hk: int
    d: int
    experts: int
    top_k: int
    f: int          # an expert's width
    lat: int        # the latent the experts work in
    fs: int         # the shared expert's width
    route_scale: float
    vocab: int
    eps: float
    held: tuple
    init_std: float
    dt_min: float
    dt_max: float
    dt_floor: float

    @property
    def layers(self):
        return len(self.pattern)

    @property
    def di(self):
        return self.h * self.p

    @property
    def cc(self):
        return self.di + 2 * self.g * self.n


def sizes(cfg):
    held = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"pattern {pattern!r} against "
                         f"{cfg['num_hidden_layers']} layers")
    return Sizes(
        pattern=pattern, c=cfg["hidden_size"], h=cfg["mamba_num_heads"],
        p=cfg["mamba_head_dim"], g=cfg["n_groups"], n=cfg["ssm_state_size"],
        k=cfg["conv_kernel"], hq=cfg["num_attention_heads"],
        hk=cfg["num_key_value_heads"], d=cfg["head_dim"],
        experts=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        f=cfg["moe_intermediate_size"], lat=cfg["moe_latent_size"],
        fs=cfg["moe_shared_expert_intermediate_size"],
        route_scale=float(cfg["routed_scaling_factor"]),
        vocab=cfg["vocab_size"], eps=float(cfg["layer_norm_epsilon"]),
        held=(int(held[0]), int(held[1])), init_std=float(cfg["init_std"]),
        dt_min=float(cfg["time_step_min"]), dt_max=float(cfg["time_step_max"]),
        dt_floor=float(cfg["time_step_floor"]))


def expert_code(li, e):
    """Where `seeded.leaf` takes a layer: expert `e` of block `li`."""
    return li + 1000 * (e + 1)


def held_ids(s):
    """The routed experts whose part of the sum is computed."""
    return tuple(range(s.held[0], s.held[0] + s.held[1]))


def top_leaves(cfg):
    s = sizes(cfg)
    return (("embeddings.weight", (s.vocab, s.c), "table"),
            ("norm_f.weight", (s.c,), "gain"),
            ("lm_head.weight", (s.vocab, s.c), "weight"))


def leaves(cfg):
    """``(name, tag, layer code, shape, kind)`` of every parameter held."""
    s = sizes(cfg)
    out = [(name, name, 0, shape, kind) for name, shape, kind in top_leaves(cfg)]
    for li, kind_of in enumerate(s.pattern):
        out += [(f"layers.{li}.{name}", name, li, shape(s), kind)
                for name, shape, kind in GROUPS[kind_of]]
        if kind_of == "E":
            for e in held_ids(s):
                out += [(f"layers.{li}." + name.replace(
                    "experts.", f"experts.{e}."), name, expert_code(li, e),
                    shape(s), kind) for name, shape, kind in EXPERT_LEAVES]
    return out


def n_params(cfg):
    return sum(math.prod(shape) for _, _, _, shape, _ in leaves(cfg))


def leaf(key, tag, layer, shape, kind, s):
    """One seeded leaf in float32 (traceable), every draw through
    `chipbench/lib/seeded.py`: a gain N(1, 0.02) and a bias N(0, 0.02) its
    own kinds; a matrix N(0, ``init_std``) (0.02 in the cell's file); the
    embedding table N(0, 1) (a token's own row then weighs as much in the
    residual as what a mixer adds to it, and the router's choice follows the
    token); and, from a uniform ``u`` made of the normal draw: the
    convolution U(-1/2, 1/2), ``A_log = log(1 + 15 u)`` (``A`` in [-16, -1]),
    ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in
    [``time_step_min``, ``time_step_max``] floored at ``time_step_floor``
    (the initialisation of the Mamba-2 reference code)."""
    import jax
    import jax.numpy as jnp

    if kind in ("gain", "bias"):
        return seeded.leaf(key, tag, layer, shape, kind)
    z = seeded.leaf(key, tag, layer, shape, "weight") \
        / seeded.KINDS["weight"][1]
    if kind == "table":
        return z
    if kind == "weight":
        return z * s.init_std
    u = 0.5 * (1.0 + jax.lax.erf(z / math.sqrt(2.0)))
    if kind == "conv":
        return u - 0.5
    if kind == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    if kind == "dt_bias":
        dt = jnp.exp(u * (math.log(s.dt_max) - math.log(s.dt_min))
                     + math.log(s.dt_min))
        dt = jnp.maximum(dt, s.dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown kind of leaf {kind!r}")


# -- the parts of a block (module-level, so that a control can alter one:
# -- chipbench/control_nemotron_h.py) -----------------------------------------

def norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def carry(state):
    """What the scan keeps of the state from one token to the next: the
    state itself, float32."""
    return state


def relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


def route_weights(chosen, scale):
    import jax.numpy as jnp

    return scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def biased(score, bias):
    """What the choice of experts is made from."""
    return score + bias


def mamba(p, u, s, q=lambda x: x, initial=None, length=None):
    """``(Mixer(u), (state, tail))`` of one request, ``u`` (T, C); the state
    and the convolution's last ``K - 1`` input rows after token ``length -
    1`` (None: the last; rows from `length` on are then padding, which
    neither decays nor adds to the state, and their outputs mean nothing).
    `initial`: what it starts from in the place of zeros."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    zxd = q(u) @ q(p["mixer.in_proj.weight"]).T
    z, xbc, dt = zxd[:, :s.di], zxd[:, s.di:s.di + s.cc], zxd[:, s.di + s.cc:]
    state0, tail0 = initial if initial is not None else (
        jnp.zeros((s.h, s.p, s.n), jnp.float32),
        jnp.zeros((s.k - 1, s.cc), jnp.float32))
    ext = jnp.concatenate([tail0, xbc], axis=0)
    w = p["mixer.conv1d.weight"]                                   # (cc, K)
    rows = jax.nn.silu(p["mixer.conv1d.bias"] + sum(
        w[:, j] * ext[j:j + t] for j in range(s.k)))
    x = rows[:, :s.di].reshape(t, s.h, s.p)
    rep = s.h // s.g
    b, c = (jnp.repeat(rows[:, lo:lo + s.g * s.n].reshape(t, s.g, s.n),
                       rep, axis=1)
            for lo in (s.di, s.di + s.g * s.n))                  # (T, H, N)
    delta = jax.nn.softplus(dt + p["mixer.dt_bias"])               # (T, H)
    if length is None:
        length = t
    delta = jnp.where(jnp.arange(t)[:, None] < length, delta, 0.0)
    a = -jnp.exp(p["mixer.A_log"])

    def token(state, row):
        x_t, b_t, c_t, d_t = row
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return carry(state), jnp.sum(state * c_t[:, None, :], axis=-1)

    state, y = jax.lax.scan(token, state0, (x, b, c, delta))
    y = y + p["mixer.D"][None, :, None] * x
    y = (y.reshape(t, s.di) * jax.nn.silu(z)).reshape(t, s.g, s.di // s.g)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + s.eps)
    y = y.reshape(t, s.di) * p["mixer.norm.weight"]
    return q(y) @ q(p["mixer.out_proj.weight"]).T, (
        state, jax.lax.dynamic_slice_in_dim(ext, length, s.k - 1, axis=0))


def attention(p, u, s, q=lambda x: x):
    """Grouped-head attention of one request, ``u`` (T, C), T a multiple of
    `Q_BLOCK`: a block of queries at a time against all T keys, causally."""
    import jax
    import jax.numpy as jnp

    t = u.shape[0]
    pos = jnp.arange(t)
    rep = s.hq // s.hk
    qry = (q(u) @ q(p["mixer.q_proj.weight"]).T).reshape(t, s.hq, s.d)
    key, val = (jnp.repeat((q(u) @ q(p[f"mixer.{n}_proj.weight"]).T).reshape(
        t, s.hk, s.d), rep, axis=1) for n in ("k", "v"))

    def block(b):
        at = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("thd,rhd->htr", q(qry[at]), q(key)) / math.sqrt(s.d)
        sc = jnp.where((pos[None, :] <= at[:, None])[None], sc, -jnp.inf)
        return jnp.einsum("htr,rhd->thd", q(jax.nn.softmax(sc, axis=-1)),
                          q(val))

    o = jax.lax.map(block, jnp.arange(t // Q_BLOCK)).reshape(t, s.hq * s.d)
    return q(o) @ q(p["mixer.o_proj.weight"]).T


def route(p, u, s):
    """``(ids (T, top_k), weights (T, top_k))``: sigmoid scores over every
    routed expert, never rounded (the control rounds matmul inputs of the
    layer's arithmetic, not the choice of experts); the choice by the biased
    score, the weights from the score itself."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.sigmoid(u @ p["mixer.gate.weight"].T)
    _, ids = jax.lax.top_k(
        biased(score, p["mixer.gate.e_score_correction_bias"]), s.top_k)
    chosen = jnp.take_along_axis(score, ids, axis=-1)
    return ids.astype(jnp.int32), route_weights(chosen, s.route_scale)


def route_margin(p, u, s):
    """(T,) how decisively each token's choice settles which of the HELD
    experts it takes, in biased scores: the least distance of a held
    expert's biased score from the boundary it would have to cross (the
    next below the chosen for a chosen expert, the last chosen for one not
    chosen). Only a token with a small margin can choose another set of
    held experts under a rounding of the router's input."""
    import jax
    import jax.numpy as jnp

    z = biased(jax.nn.sigmoid(u @ p["mixer.gate.weight"].T),
               p["mixer.gate.e_score_correction_bias"])
    top = jax.lax.top_k(z, s.top_k + 1)[0]
    last_in, first_out = top[:, s.top_k - 1:s.top_k], top[:, s.top_k:]
    mine = z[:, s.held[0]:s.held[0] + s.held[1]]
    return jnp.min(jnp.where(mine >= last_in, mine - first_out,
                             last_in - mine), axis=-1)


def shared_expert(p, u, q=lambda x: x):
    h = relu2(q(u) @ q(p["mixer.shared_experts.up_proj.weight"]).T)
    return q(h) @ q(p["mixer.shared_experts.down_proj.weight"]).T


def expert(p, v, q=lambda x: x):
    """One routed expert over latent rows ``v``: ungated, ``relu^2``."""
    h = relu2(q(v) @ q(p["mixer.experts.up_proj.weight"]).T)
    return q(h) @ q(p["mixer.experts.down_proj.weight"]).T


@functools.lru_cache(maxsize=None)
def _programs(s, dtype):
    """The programs of one (sizes, dtype)."""
    import jax
    import jax.numpy as jnp

    q = lower.ROUND[dtype]      # "int8": every matmul's two inputs rounded
    hi = functools.partial(jax.default_matmul_precision, "highest")

    def weights(group):
        def make(key, code):
            return {name: leaf(key, name, code, shape(s), kind, s)
                    for name, shape, kind in group}
        return jax.jit(make)

    @jax.jit
    def embed(key, tokens):
        return leaf(key, "embeddings.weight", 0, (s.vocab, s.c), "table",
                    s)[tokens]

    @jax.jit
    def mamba_step(p, x, initial, length):
        with hi():
            f, final = mamba(p, norm(x, p["norm.weight"], s.eps), s, q,
                             initial, length)
            return x + f, final

    @jax.jit
    def attn_step(p, x):
        with hi():
            return x + attention(p, norm(x, p["norm.weight"], s.eps), s, q)

    @jax.jit
    def route_step(p, x):
        """The choice of experts, the latent rows, the shared expert's
        part."""
        with hi():
            u = norm(x, p["norm.weight"], s.eps)
            ids, w = route(p, u, s)
            v = q(u) @ q(p["mixer.fc1_latent_proj.weight"]).T
            return ids, w, v, shared_expert(p, u, q)

    @jax.jit
    def margin_step(p, x):
        with hi():
            return route_margin(p, norm(x, p["norm.weight"], s.eps), s)

    @jax.jit
    def expert_step(p, v, ids, w, acc, e):
        """Expert `e` over every token's latent row, added under its weight
        where the token chose it (a mask; weight 0 elsewhere)."""
        with hi():
            mine = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
            return acc + mine[:, None] * expert(p, v, q)

    @jax.jit
    def finish(p, x, acc, shared):
        with hi():
            return x + q(acc) @ q(p["mixer.fc2_latent_proj.weight"]).T \
                + shared

    @jax.jit
    def head(key, rows):
        wh = leaf(key, "lm_head.weight", 0, (s.vocab, s.c), "weight", s)
        with hi():
            z = norm(rows, leaf(key, "norm_f.weight", 0, (s.c,), "gain", s),
                     s.eps)
            return (q(z) @ q(wh).T).astype(jnp.float32)

    return dict(M=weights(MAMBA_LEAVES), A=weights(ATTN_LEAVES),
                E=weights(SHARED_LEAVES), expert=weights(EXPERT_LEAVES),
                embed=embed, mamba_step=mamba_step, attn_step=attn_step,
                route_step=route_step, margin_step=margin_step,
                expert_step=expert_step, finish=finish, head=head)


def forward(cfg, seed, tokens, lengths, dtype="float32", margins=None,
            initial=None, finals=None):
    """The last block's residual rows of each request: a list of ``(T_b,
    C)`` device arrays, ``T_b`` request b's `lengths` rounded up to
    `Q_BLOCK` (`tokens` (B, T), right-padded; nothing after a position
    reaches it). A list given as `margins` gains, an expert layer, the
    requests' `route_margin` (host arrays ``(T_b,)``). `initial`: ``{(block,
    request): (state, tail)}`` that a Mamba block starts from in the place
    of zeros. A dict given as `finals` gains ``{(block, request): (state,
    tail)}`` after each request's position ``lengths[b] - 1``: what the
    request leaves in its slot."""
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
    for li, kind in enumerate(s.pattern):
        code = jnp.int32(li)
        if kind == "M":
            p = pr["M"](key, code)
            for b in range(len(x)):
                # the state after the request's own last token: the
                # padding after it changes nothing
                x[b], final = pr["mamba_step"](
                    p, x[b], (initial or {}).get((li, b)),
                    jnp.int32(lengths[b]))
                if finals is not None:
                    finals[li, b] = final
        elif kind == "*":
            p = pr["A"](key, code)
            x = [pr["attn_step"](p, xb) for xb in x]
        else:
            p = pr["E"](key, code)
            routed = [pr["route_step"](p, xb) for xb in x]
            if margins is not None:
                margins.append([onp.asarray(pr["margin_step"](p, xb))
                                for xb in x])
            acc = [jnp.zeros_like(r[2]) for r in routed]
            for e in held_ids(s):
                pe = pr["expert"](key, jnp.int32(expert_code(li, e)))
                acc = [pr["expert_step"](pe, r[2], r[0], r[1], ab,
                                         jnp.int32(e))
                       for r, ab in zip(routed, acc)]
            x = [pr["finish"](p, xb, ab, r[3])
                 for xb, ab, r in zip(x, acc, routed)]
        del p
    return x


def logits_at(cfg, seed, tokens, rows, dtype="float32", with_margin=False,
              initial=None):
    """Logits of the reference at chosen positions.

    `tokens` is an int array (B, T), right-padded; `rows` lists ``(b, t)``
    pairs. Returns a float32 numpy array (len(rows), vocabulary). Each
    request is computed as far as the last of its rows. `with_margin`: also
    a (len(rows),) array, the least `route_margin` of the row's token over
    the expert layers (inf where there is none). `initial`: `forward`'s."""
    import jax.numpy as jnp
    import numpy as onp

    s = sizes(cfg)
    rows = onp.asarray(rows, onp.int32).reshape(-1, 2)
    n_b = onp.asarray(tokens).shape[0]
    lengths = [0] * n_b
    for b, t in rows:
        lengths[b] = max(lengths[b], int(t) + 1)
    margins = [] if with_margin else None
    x = forward(cfg, seed, tokens, lengths, dtype, margins, initial)
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
