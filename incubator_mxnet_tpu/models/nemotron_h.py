"""Nemotron-H: a decoder whose blocks differ in KIND by a pattern string
(``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E`` a latent expert
layer, ``*`` attention), under the names of the release's ``config.json``
(``model_type`` ``nemotron_h``).

Every block is ONE mixer behind one RMSNorm, a token's residual ``x`` in
float32, matmuls `dtype` (bfloat16 as published) into float32::

    x = x + Mixer_l(N_l(x))            N(x) = x / sqrt(mean(x^2) + eps) * g

and after the last block a final norm and an untied head. No positional term
anywhere: attention applies no rotary (the Mamba layers carry order).

**Mamba-2** (``M``): ``[z ; xBC ; dt] = u W_in``; ``xBC`` through a causal
depthwise convolution of ``conv_kernel`` rows with bias and a SiLU; split ``x
(H, P)``, ``B`` and ``C`` ``(G, N)`` each; ``Delta = softplus(dt + dt_bias)``,
``A = -exp(A_log)``; per head ``S_t = exp(Delta A) S_{t-1} + Delta x_t (x)
B_t``, ``y_t = S_t C_t + D x_t`` (`ops.ssm`); ``y`` gated by ``silu(z)``
FIRST, then an RMSNorm over each of the ``G`` groups' values with one gain;
``out = y W_out``.
What a slot keeps is the state ``(H, P, N)`` float32 (stored as
`ops.ssm.state_store_shape` says) and the convolution's tail of ``conv_kernel
- 1`` rows: a fixed size whatever the context.

**Attention** (``*``): grouped heads (``num_attention_heads`` over
``num_key_value_heads``), no bias, scale ``1 / sqrt(head_dim)``, causal, full;
a token leaves keys and values of the stored heads in pages.

**Expert layer** (``E``, "LatentMoE"): ``s = sigmoid(u W_r)`` over all routed
experts in float32, the ``num_experts_per_tok`` largest of ``s + b`` chosen
(``b`` the score-correction bias: choice only), ``w = routed_scaling_factor *
s_chosen / (sum s_chosen + 1e-20)``; ``v = u W_dn`` into the latent
(``moe_latent_size``); ``E_e(v) = relu(v W1_e)^2 W2_e``; ``out = (sum_e w_e
E_e(v)) W_up + relu(u S1)^2 S2`` (the shared expert at full width). A decoder
may hold a contiguous share of the routed experts (`experts_held = (first,
count)`): the router keeps all its outputs and the top-k is over all of them,
and what the absent experts would add is left out (`ops.moe`).

A layer is handed the **cache-access object** of the program that runs it
(`serve/ssm.py`)::

    cache.attend(page_layer, q, k, v) -> o        # `*`: pages
    cache.mix(state_layer, fn) -> y               # `M`: fn(state, tail) ->
                                                  #   (y, state', tail')
    cache.valid, cache.step, cache.count_experts  # `E`, as `models/pangu.py`

`layer_kinds` says what each block keeps in a slot (``"state"``, ``"pages"``
or None) — the one place the serving programs ask how many layers there are
and which hold pages. The leaves are held in the form the matmuls read
(matrices ``(in, out)``, the held experts stacked ``(held, in, out)``, the
convolution ``(kernel, channels)``); gains, the router and its bias, the
convolution, ``A_log``, ``D`` and ``dt_bias`` float32. Multi-token prediction
(``num_nextn_predict_layers``) is not held.
"""
from __future__ import annotations

from dataclasses import dataclass

from .pangu import rms_gain

__all__ = ["NemotronHConfig", "NemotronHDecoder"]

TOP_LEAVES = ("embed", "norm", "head")
KINDS = {"M": "state", "*": "pages", "E": None}
#: kept float32 whatever the decoder's `dtype`
FLOAT32 = frozenset({"norm", "n", "conv_w", "conv_b", "dt_bias", "a_log",
                     "d", "g_norm", "w_router", "b_router"})


@dataclass(frozen=True)
class NemotronHConfig:
    """Sizes under the names of the release's ``config.json``, and
    `experts_held`: ``(first, count)`` of the routed experts this decoder
    holds (None: all of them)."""

    num_hidden_layers: int = 88
    hybrid_override_pattern: str = ""
    hidden_size: int = 4096
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    vocab_size: int = 131072
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    experts_held: tuple = None

    @classmethod
    def from_dict(cls, cfg):
        kw = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        if kw.get("experts_held") is not None:
            kw["experts_held"] = tuple(int(v) for v in kw["experts_held"])
        return cls(**kw)

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or set(pattern) - set(KINDS):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} does not name "
                f"{self.num_hidden_layers} blocks of kinds {sorted(KINDS)}")

    @property
    def held(self):
        """``(first, count)`` of the routed experts held."""
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self):
        """``[x ; B ; C]``: what the convolution runs over."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def layer_shapes(self, li):
        """``{leaf: shape}`` of block `li` in the stored form."""
        c = self.hidden_size
        kind = self.hybrid_override_pattern[li]
        if kind == "M":
            di, cc, h = self.d_inner, self.conv_channels, self.mamba_num_heads
            return {"n": (c,), "w_in": (c, di + cc + h),
                    "conv_w": (self.conv_kernel, cc), "conv_b": (cc,),
                    "dt_bias": (h,), "a_log": (h,), "d": (h,),
                    "g_norm": (di,), "w_out": (di, c)}
        if kind == "*":
            hq, hk, d = (self.num_attention_heads, self.num_key_value_heads,
                         self.head_dim)
            return {"n": (c,), "w_qkv": (c, (hq + 2 * hk) * d),
                    "w_o": (hq * d, c)}
        lat, f, fs = (self.moe_latent_size, self.moe_intermediate_size,
                      self.moe_shared_expert_intermediate_size)
        held = self.held[1]
        return {"n": (c,), "w_router": (c, self.n_routed_experts),
                "b_router": (self.n_routed_experts,), "w_dn": (c, lat),
                "we_1": (held, lat, f), "we_2": (held, f, lat),
                "w_up": (lat, c), "ws_1": (c, fs), "ws_2": (fs, c)}

    def top_shapes(self):
        c = self.hidden_size
        return {"embed": (self.vocab_size, c), "norm": (c,),
                "head": (c, self.vocab_size)}


class NemotronHDecoder:
    """The family's weights, held once in the stored form, and its blocks.

    `params`: ``{"embed": (V, C), "norm": (C,), "head": (C, V), "layers":
    [{leaf: array}, ...]}`` with each block's leaves as
    `NemotronHConfig.layer_shapes` says. Matrices are cast to `dtype` (the
    arrays handed in are not kept); `FLOAT32` leaves stay float32."""

    family = "nemotron_h"

    def __init__(self, config, params, dtype="bfloat16"):
        import jax.numpy as jnp

        self.config = cfg = config
        self.dtype = jnp.dtype(dtype)
        if len(params["layers"]) != cfg.num_hidden_layers:
            raise ValueError(
                f"{len(params['layers'])} layers given, the configuration "
                f"has {cfg.num_hidden_layers}")
        first, count = cfg.held
        if not (0 <= first and count >= 1
                and first + count <= cfg.n_routed_experts):
            raise ValueError(
                f"experts_held {cfg.held} lies outside the "
                f"{cfg.n_routed_experts} routed experts")

        def take(name, a, shape, where=""):
            if tuple(a.shape) != tuple(shape):
                raise ValueError(f"{where}{name}: got {tuple(a.shape)}, the "
                                 f"configuration says {tuple(shape)}")
            return jnp.asarray(
                a, jnp.float32 if name in FLOAT32 else self.dtype)

        top = cfg.top_shapes()
        self._params = {n: take(n, params[n], top[n]) for n in TOP_LEAVES}
        self._params["layers"] = tuple(
            {n: take(n, lp[n], shape, f"layers.{i}.")
             for n, shape in cfg.layer_shapes(i).items()}
            for i, lp in enumerate(params["layers"]))
        self._max_length = cfg.max_position_embeddings
        kinds = self.layer_kinds()
        # a block's index among the blocks of its kind: which pool leaf,
        # which state leaf
        self._nth = [kinds[:i].count(k) for i, k in enumerate(kinds)]

    def _auto_refresh(self):
        """The engine's hot-swap seam: these weights are the decoder's own
        and do not change under it."""

    # -- what the serving programs ask ----------------------------------------

    def layer_kinds(self):
        """What each block keeps in a slot: ``"state"``, ``"pages"`` or
        None (an expert layer keeps nothing)."""
        return tuple(KINDS[k] for k in self.config.hybrid_override_pattern)

    def kv_geometry(self):
        """``(page layers, stored heads, head size, dtype)`` of the K/V rows
        the attention blocks leave in pages."""
        cfg = self.config
        return (self.layer_kinds().count("pages"), cfg.num_key_value_heads,
                cfg.head_dim, self.dtype)

    def state_geometry(self):
        """``(state layers, {leaf kind: (a slot's shape, dtype)})`` of what
        the Mamba blocks keep in a slot: the recurrent state in float32, as
        `ops.ssm` stores it, and the convolution's tail in the matmuls'
        dtype."""
        import numpy as onp

        from ..ops.ssm import state_store_shape

        cfg = self.config
        return (self.layer_kinds().count("state"), {
            "ssm": (state_store_shape(
                cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
                cfg.n_groups), onp.dtype("float32")),
            "conv": ((cfg.conv_kernel - 1, cfg.conv_channels),
                     onp.dtype(self.dtype))})

    @property
    def expert_layers(self):
        return self.config.hybrid_override_pattern.count("E")

    # -- the mathematics (traced) --------------------------------------------

    def embed(self, params, tokens, pos):  # noqa: ARG002
        """``tokens`` (N, T) or (N,): ``x`` (N T, C) float32, rows in that
        order (no positional term)."""
        import jax.numpy as jnp

        return params["embed"][tokens.reshape(-1)].astype(jnp.float32)

    def layer_params(self, params, li):
        return params["layers"][li]

    def _mm(self, a, w):
        import jax.numpy as jnp

        return jnp.matmul(a.astype(self.dtype), w,
                          preferred_element_type=jnp.float32)

    def mamba(self, lp, u, cache, nth):
        """The Mamba-2 mixer over rows ``u`` (T, C): a decode step's row a
        slot, or one slot's chunk."""
        import jax
        import jax.numpy as jnp

        from ..ops import ssm

        cfg = self.config
        t = u.shape[0]
        h, p, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                      cfg.ssm_state_size)
        di, cc = cfg.d_inner, cfg.conv_channels
        zxd = self._mm(u, lp["w_in"])
        z, xbc, dt = zxd[:, :di], zxd[:, di:di + cc], zxd[:, di + cc:]
        dt = jax.nn.softplus(dt + lp["dt_bias"])
        a = -jnp.exp(lp["a_log"])

        def split(rows):
            rows = jax.nn.silu(rows)
            return (rows[:, :di].reshape(t, h, p),
                    rows[:, di:di + g * n].reshape(t, g, n),
                    rows[:, di + g * n:].reshape(t, g, n))

        def decode(state, tail):
            rows, tail = ssm.conv_decode(tail, xbc, lp["conv_w"],
                                         lp["conv_b"], cache.valid)
            with cache.eng._mesh_scope():
                y, state = ssm.ssm_decode(state, *split(rows), dt, a,
                                          lp["d"], cache.valid)
            return y, state, tail

        def chunk(state, tail):
            rows, tail = ssm.conv_chunk(tail, xbc, lp["conv_w"],
                                        lp["conv_b"], cache.t_len)
            y, state = ssm.ssm_chunk(state, *split(rows), dt, a, lp["d"],
                                     cache.valid, block=cfg.chunk_size)
            return y, state, tail

        y = cache.mix(nth, decode if cache.step == "decode" else chunk)
        # gate first, then a norm over each group's values
        y = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, g, di // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.layer_norm_epsilon)
        return self._mm(y.reshape(t, di) * lp["g_norm"], lp["w_out"])

    def attention(self, lp, u, cache, nth):
        """Grouped-head attention over rows ``u`` (T, C), no rotary."""
        cfg = self.config
        t = u.shape[0]
        hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        qkv = self._mm(u, lp["w_qkv"])
        q = qkv[:, :hq * d].reshape(t, hq, d)
        k = qkv[:, hq * d:(hq + hk) * d].reshape(t, hk, d)
        v = qkv[:, (hq + hk) * d:].reshape(t, hk, d)
        o = cache.attend(nth, q, k, v)                          # (T, Hq, d)
        return self._mm(o.reshape(t, hq * d), lp["w_o"])

    def experts(self, li, lp, u, cache):
        """The latent expert layer over rows ``u`` (T, C); the layer's int32
        ``(held pairs, distinct held experts hit)`` goes to `cache`."""
        import jax.numpy as jnp

        from ..ops import moe

        cfg = self.config
        ids, weights = moe.route(u, lp["w_router"], cfg.num_experts_per_tok,
                                 cfg.routed_scaling_factor,
                                 bias=lp["b_router"])
        with cache.eng._mesh_scope():
            routed, stats = moe.held_experts(
                self._mm(u, lp["w_dn"]).astype(self.dtype), ids, weights,
                (lp["we_1"], lp["we_2"]), cfg.held, cache.valid,
                step=cache.step, routed=cfg.n_routed_experts)
        cache.count_experts(li, stats)
        shared = jnp.square(jnp.maximum(self._mm(u, lp["ws_1"]), 0.0))
        return self._mm(routed, lp["w_up"]) + self._mm(shared, lp["ws_2"])

    def layer(self, li, lp, x, pos, cache):  # noqa: ARG002
        """One block: ``x`` (T, C) float32; `cache` as the module docstring
        says. Returns ``x'`` (T, C) float32."""
        kind, nth = self.config.hybrid_override_pattern[li], self._nth[li]
        u = rms_gain(x, lp["n"], self.config.layer_norm_epsilon)
        if kind == "M":
            return x + self.mamba(lp, u, cache, nth)
        if kind == "*":
            return x + self.attention(lp, u, cache, nth)
        return x + self.experts(li, lp, u, cache)

    def next_logits(self, params, x):
        """``(..., V)`` float32."""
        import jax.numpy as jnp

        z = rms_gain(x, params["norm"], self.config.layer_norm_epsilon)
        return jnp.matmul(z.astype(self.dtype), params["head"],
                          preferred_element_type=jnp.float32)
