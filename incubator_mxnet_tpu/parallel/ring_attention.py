"""Ring attention — sequence-parallel exact attention for long contexts
(Liu et al., "Ring Attention with Blockwise Transformers"; the TPU-native
replacement for the reference's single-device fused attention at sequence
lengths that exceed one chip's HBM — reference role:
`src/operator/subgraph/dnnl/dnnl_transformer_qk_property.h`).

Each device on the `axis_name` ring holds one sequence shard of Q, K, V
(layout (B, H, T_local, D), matching `ops/flash_attention.py`). K/V blocks
rotate around the ring with `lax.ppermute` (neighbor ICI hops) while each
device accumulates its Q block's attention over every K/V block with the
numerically-stable online-softmax recurrence — communication overlaps with
the per-block attention compute, memory stays O(T_local).

Call INSIDE shard_map/pjit (like `parallel/collectives.py`);
`ring_self_attention` is the NDArray-level convenience that builds the
shard_map over the active mesh.
"""
from __future__ import annotations

import math
from functools import partial

__all__ = ["ring_attention", "ring_self_attention"]


def ring_attention(q, k, v, axis_name, causal=False, sm_scale=None):
    """Exact attention over a sequence sharded on `axis_name`.

    q, k, v: (B, H, T_local, D) jax arrays (this device's sequence shard).
    Returns (B, H, T_local, D): attention output for the local Q block
    against the FULL (global) sequence.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from . import collectives

    b, h, t_local, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    n = collectives.axis_size(axis_name)
    my = lax.axis_index(axis_name)

    neg_inf = jnp.asarray(-jnp.inf, jnp.float32)
    q32 = q.astype(jnp.float32)

    def block_update(carry, kv_src_idx, k_blk, v_blk):
        o, m, l = carry
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       k_blk.astype(jnp.float32)) * sm_scale
        if causal:
            q_pos = my * t_local + jnp.arange(t_local)
            k_pos = kv_src_idx * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, neg_inf)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # rows fully masked so far keep m=-inf; guard the exp shift
        shift = jnp.where(jnp.isneginf(m_new), 0.0, m - m_new)
        p = jnp.exp(s - jnp.where(jnp.isneginf(m_new), 0.0,
                                  m_new)[..., None])
        p = jnp.where(jnp.isneginf(m_new)[..., None], 0.0, p)
        alpha = jnp.exp(shift)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = (o * alpha[..., None]
                 + jnp.einsum("bhqk,bhkd->bhqd", p,
                              v_blk.astype(jnp.float32)))
        return o_new, m_new, l_new

    perm = None  # built lazily from the concrete axis size

    def body(i, carry):
        o, m, l, k_blk, v_blk = carry
        kv_src = (my - i) % n  # whose block we currently hold
        o, m, l = block_update((o, m, l), kv_src, k_blk, v_blk)
        # rotate K/V to the next device (skippable on the last step, but a
        # static-trip fori_loop keeps the loop body uniform; XLA overlaps
        # the permute with the next block's einsum)
        k_blk = collectives.ring_permute(k_blk, axis_name)
        v_blk = collectives.ring_permute(v_blk, axis_name)
        return o, m, l, k_blk, v_blk

    # initial accumulators must carry the shard_map device-varying type of
    # the loop outputs (they depend on axis_index after one trip)
    o0 = collectives.pvary(jnp.zeros((b, h, t_local, d), jnp.float32),
                           (axis_name,))
    m0 = collectives.pvary(jnp.full((b, h, t_local), -jnp.inf, jnp.float32),
                           (axis_name,))
    l0 = collectives.pvary(jnp.zeros((b, h, t_local), jnp.float32),
                           (axis_name,))
    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q, k, v, mesh=None, axis="sp", causal=False,
                        sm_scale=None):
    """NDArray-level ring attention: shards the sequence dim of
    (B, H, T, D) inputs over `axis` of the active mesh and runs
    `ring_attention` under shard_map."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..ndarray.ndarray import NDArray
    from .mesh import current_mesh

    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("ring_self_attention needs a mesh (pass mesh= or "
                         "enter a mesh_scope)")
    qv = q._data if isinstance(q, NDArray) else q
    kv = k._data if isinstance(k, NDArray) else k
    vv = v._data if isinstance(v, NDArray) else v

    spec = P(None, None, axis, None)  # shard T of (B, H, T, D)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis, causal=causal,
                sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = fn(qv, kv, vv)
    return NDArray(out) if isinstance(q, NDArray) else out
