"""openPangu-Ultra-MoE: latent attention (MLA), sandwich norms, and a dropless
sigmoid-routed expert layer, as the release's ``config.json`` names them
(``kv_lora_rank``, ``q_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``sandwich_norm``, ``n_routed_experts``, ``num_experts_per_tok``,
``routed_scaling_factor``, ``first_k_dense_replace``).

The block, a token's residual ``x`` in float32, matmuls `dtype` (bfloat16 as
published) into float32, every ``N_*`` an RMSNorm with its own gain
(``x / sqrt(mean(x^2) + eps) * g``)::

    x = x + N_post_attn(Attn(N_in(x)))
    x = x + N_post_mlp(F(N_pre_mlp(x)))

**Attention** is latent: ``c_q = N_q(u W_qa)``, ``q_h = c_q W_qb = [q_nope_h ;
q_rope_h]`` a head; ``[c ; k_r] = u W_kva``, ``c_kv = N_kv(c)``, ``k_rope =
RoPE(k_r)`` (one for all heads; half-split pairing, `models.evabyte.rope`);
``[k_nope_h ; v_h] = c_kv W_kvb,h``; score ``(q_nope_h . k_nope_h +
RoPE(q_rope_h) . k_rope) / sqrt(nope + rope)``, causal softmax in float32,
``Attn = concat_h(sum p v_h) W_o``. What a token leaves in the cache is the one
row ``[c_kv ; k_rope]``, shared by every head. The layer hands that row and the
queries to the **cache-access object** of the program that runs it::

    cache.attend(li, lp, q_nope, q_rope, latent) -> o      # (T, H, v)
    cache.valid                                            # (T,) real rows
    cache.step                                             # "decode" | "chunk"
    cache.count_experts(li, stats)

which stores the row and attends as suits the program: a decode step
*absorbed* (``q~_h = q_nope_h W_uk,h`` scored against ``c_kv`` itself, the
weighted sum of ``c_kv`` put through ``W_uv,h`` after), a prefill chunk
*up-projected* (the rows it attends expanded to per-head keys and values):
`serve/mla.py`.

``F`` of the leading ``first_k_dense_replace`` layers is a gated SiLU; of the
others ``E_shared(u) + sum_e w_e E_e(u)`` over the ``num_experts_per_tok``
largest of ``sigmoid(u W_r)`` (`ops.moe`). A decoder may hold a contiguous
share of the routed experts (`experts_held = (first, count)`): the router keeps
all its outputs and the top-k is over all of them, and what the absent experts
would add is left out — an expert-parallel deployment's one chip, before the
exchange. Embedding and head are untied; logits are float32.

**The leaves, in the form the matmuls read** (`PanguConfig.leaf_shapes`;
`stored` turns a checkpoint-layout leaf into it, once, at load): matrices
``(in, out)``; ``W_qb``'s columns regrouped so that every head's ``nope`` part
comes before every head's ``rope`` part (two aligned slices, no per-head
re-layout in the step); ``W_kvb`` split into ``w_uk`` ``(H, nope, rank)`` and
``w_uv`` ``(H, rank, v)``, the two batched products of the absorbed form and,
read the other way, of the up-projection; the held experts stacked ``(held, in,
out)``; the router float32. Multi-token prediction (``num_nextn_predict_layers``)
is not held: the module follows the last layer (ROADMAP R7).
"""
from __future__ import annotations

from dataclasses import dataclass

from .evabyte import rope

__all__ = ["PanguConfig", "PanguDecoder", "rms_gain", "stored"]

TOP_LEAVES = ("embed", "norm", "head")
ATTN_LEAVES = ("n_in", "w_qa", "n_q", "w_qb", "w_kva", "n_kv", "w_uk", "w_uv",
               "w_o", "n_post_attn", "n_pre_mlp", "n_post_mlp")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("w_router", "ws_gate", "ws_up", "ws_down", "we_gate",
                 "we_up", "we_down")
#: kept float32 whatever the decoder's `dtype`: the norms' gains, and the
#: router (its scores choose experts; a rounding there is another choice)
FLOAT32 = frozenset(n for n in ATTN_LEAVES if n.startswith("n_")) \
    | {"norm", "w_router"}


@dataclass(frozen=True)
class PanguConfig:
    """Sizes under the names of the release's ``config.json``, and
    `experts_held`: ``(first, count)`` of the routed experts this decoder
    holds (None: all of them)."""

    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    hidden_size: int = 7680
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    vocab_size: int = 153600
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    experts_held: tuple = None

    @classmethod
    def from_dict(cls, cfg):
        kw = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        if kw.get("experts_held") is not None:
            kw["experts_held"] = tuple(int(v) for v in kw["experts_held"])
        return cls(**kw)

    @property
    def held(self):
        """``(first, count)`` of the routed experts held."""
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def latent_width(self):
        """Values a token leaves in the cache a layer: ``[c_kv ; k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_dense(self, li):
        return li < self.first_k_dense_replace

    def leaf_shapes(self):
        """``({top leaf: shape}, {attention leaf: shape}, {dense FFN leaf:
        shape}, {expert FFN leaf: shape})`` in the stored form."""
        c, f, fm = (self.hidden_size, self.intermediate_size,
                    self.moe_intermediate_size)
        h, dn, dr, dv = (self.num_attention_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim)
        rq, r = self.q_lora_rank, self.kv_lora_rank
        fs = fm * self.n_shared_experts
        held = self.held[1]
        top = {"embed": (self.vocab_size, c), "norm": (c,),
               "head": (c, self.vocab_size)}
        attn = {"n_in": (c,), "w_qa": (c, rq), "n_q": (rq,),
                "w_qb": (rq, h * (dn + dr)), "w_kva": (c, r + dr),
                "n_kv": (r,), "w_uk": (h, dn, r), "w_uv": (h, r, dv),
                "w_o": (h * dv, c), "n_post_attn": (c,), "n_pre_mlp": (c,),
                "n_post_mlp": (c,)}
        dense = {"w_gate": (c, f), "w_up": (c, f), "w_down": (f, c)}
        expert = {"w_router": (c, self.n_routed_experts),
                  "ws_gate": (c, fs), "ws_up": (c, fs), "ws_down": (fs, c),
                  "we_gate": (held, c, fm), "we_up": (held, c, fm),
                  "we_down": (held, fm, c)}
        return top, attn, dense, expert

    def layer_shapes(self, li):
        """``{leaf: shape}`` of layer `li`."""
        _, attn, dense, expert = self.leaf_shapes()
        return {**attn, **(dense if self.is_dense(li) else expert)}


def stored(cfg, name, a):
    """A checkpoint-layout leaf (a matrix ``(out, in)``, as the release's
    ``nn.Linear`` keeps it) in the stored form. `name`: ``w_qb`` for
    ``q_b_proj`` ``(H (nope + rope), q_rank)``; ``w_uk`` / ``w_uv`` for
    ``kv_b_proj`` ``(H (nope + v), rank)``, of which each takes its part; any
    other matrix is turned; gains and tables are as they are."""
    import jax.numpy as jnp

    h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    if name == "w_qb":
        w = a.reshape(h, dn + dr, -1)
        return jnp.concatenate([w[:, :dn].reshape(h * dn, -1),
                                w[:, dn:].reshape(h * dr, -1)], 0).T
    if name == "w_uk":                      # (H, nope, rank)
        return a.reshape(h, dn + dv, -1)[:, :dn]
    if name == "w_uv":                      # (H, rank, v)
        return jnp.swapaxes(a.reshape(h, dn + dv, -1)[:, dn:], 1, 2)
    if name in ("embed", "norm") or name.startswith("n_"):
        return a
    return jnp.swapaxes(a, -1, -2)


def rms_gain(x, g, eps):
    """RMSNorm with a plain gain, in float32."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) \
        * g.astype(jnp.float32)


class PanguDecoder:
    """The family's weights, held once in the stored form, and its layer.

    `params`: ``{"embed": (V, C), "norm": (C,), "head": (C, V), "layers":
    [{leaf: array}, ...]}`` with each layer's leaves as
    `PanguConfig.layer_shapes` says (`stored` makes them from a checkpoint's).
    Matrices are cast to `dtype` (the arrays handed in are not kept); gains
    and the router stay float32."""

    family = "pangu_moe"

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

        top = cfg.leaf_shapes()[0]
        self._params = {n: take(n, params[n], top[n]) for n in TOP_LEAVES}
        self._params["layers"] = tuple(
            {n: take(n, lp[n], shape, f"layers.{i}.")
             for n, shape in cfg.layer_shapes(i).items()}
            for i, lp in enumerate(params["layers"]))
        self._max_length = cfg.max_position_embeddings

    def _auto_refresh(self):
        """The engine's hot-swap seam: these weights are the decoder's own
        and do not change under it."""

    def layer_kinds(self):
        """What each layer keeps in a slot (`serve/engine.py`): pages, all."""
        return ("pages",) * self.config.num_hidden_layers

    def kv_geometry(self):
        """``(layers, "latent", row width, dtype)``: a page of this family
        is one leaf of latent rows shared by all heads (`serve/pages.py`)."""
        cfg = self.config
        return (cfg.num_hidden_layers, "latent", cfg.latent_width, self.dtype)

    @property
    def expert_layers(self):
        cfg = self.config
        return cfg.num_hidden_layers - min(cfg.num_hidden_layers,
                                           cfg.first_k_dense_replace)

    # -- the mathematics (traced) --------------------------------------------

    def embed(self, params, tokens, pos):  # noqa: ARG002
        """``tokens`` (N, T) or (N,): ``x`` (N T, C) float32, rows in that
        order (positions enter in `layer`, rotary)."""
        import jax.numpy as jnp

        return params["embed"][tokens.reshape(-1)].astype(jnp.float32)

    def layer_params(self, params, li):
        return params["layers"][li]

    def _mm(self, a, w):
        import jax.numpy as jnp

        return jnp.matmul(a.astype(self.dtype), w,
                          preferred_element_type=jnp.float32)

    def project(self, lp, x, pos):
        """What attention takes of ``x`` (T, C) at positions ``pos`` (T,):
        ``(q_nope (T, H, nope), q_rope (T, H, rope) rotated, latent (T, rank +
        rope))``, float32."""
        import jax.numpy as jnp

        cfg, eps = self.config, self.config.rms_norm_eps
        t = x.shape[0]
        h, dn, dr, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim, cfg.kv_lora_rank)
        u = rms_gain(x, lp["n_in"], eps)
        q = self._mm(rms_gain(self._mm(u, lp["w_qa"]), lp["n_q"], eps),
                     lp["w_qb"])
        q_nope = q[:, :h * dn].reshape(t, h, dn)
        q_rope = rope(q[:, h * dn:].reshape(t, h, dr), pos, cfg.rope_theta)
        kv = self._mm(u, lp["w_kva"])
        k_rope = rope(kv[:, None, r:], pos, cfg.rope_theta)[:, 0]
        latent = jnp.concatenate(
            [rms_gain(kv[:, :r], lp["n_kv"], eps), k_rope], -1)
        return q_nope, q_rope, latent

    def ffn(self, li, lp, u, valid, step="decode"):
        """``F(u)`` of layer `li` and, for an expert layer, its int32
        ``(held pairs, distinct held experts hit)`` (else None). Rows that
        are not `valid` choose no expert; `step` is the kind of step
        (`ops.moe.KERNEL_NAMES`)."""
        import jax

        from ..ops import moe

        cfg = self.config

        def gated(wg, wu, wd):
            return self._mm(jax.nn.silu(self._mm(u, wg)) * self._mm(u, wu), wd)

        if self.config.is_dense(li):
            return gated(lp["w_gate"], lp["w_up"], lp["w_down"]), None
        ids, weights = moe.route(u, lp["w_router"], cfg.num_experts_per_tok,
                                 cfg.routed_scaling_factor)
        routed, stats = moe.held_experts(
            u.astype(self.dtype), ids, weights,
            (lp["we_gate"], lp["we_up"], lp["we_down"]), cfg.held, valid,
            step=step, routed=cfg.n_routed_experts)
        return gated(lp["ws_gate"], lp["ws_up"], lp["ws_down"]) + routed, stats

    def layer(self, li, lp, x, pos, cache):
        """One block: ``x`` (T, C) float32, ``pos`` the rows' positions (T
        of them, in any shape), `cache` as the module docstring says. Returns
        ``x'`` (T, C) float32."""
        cfg, eps = self.config, self.config.rms_norm_eps
        t = x.shape[0]
        q_nope, q_rope, latent = self.project(lp, x, pos.reshape(-1))
        o = cache.attend(li, lp, q_nope, q_rope, latent)
        o = self._mm(o.reshape(t, cfg.num_attention_heads * cfg.v_head_dim),
                     lp["w_o"])
        h = x + rms_gain(o, lp["n_post_attn"], eps)
        f, stats = self.ffn(li, lp, rms_gain(h, lp["n_pre_mlp"], eps),
                            cache.valid, cache.step)
        if stats is not None:
            cache.count_experts(li, stats)
        return h + rms_gain(f, lp["n_post_mlp"], eps)

    def next_logits(self, params, x):
        """``(..., V)`` float32."""
        import jax.numpy as jnp

        z = rms_gain(x, params["norm"], self.config.rms_norm_eps)
        return jnp.matmul(z.astype(self.dtype), params["head"],
                          preferred_element_type=jnp.float32)
