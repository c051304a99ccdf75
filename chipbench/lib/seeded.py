"""Every weight of every cell comes from ``--seed`` through this file.

One value per (seed, tag, layer, shape, kind): threefry bits, so the same call
gives the same numbers inside any jitted program, on the chip and on the CPU.
The program's side fills the Gluon parameters with these values leaf by leaf
(`fill`); a reference asks for the same leaves again by name, inside its own
programs, and so takes nothing that the program has held.
"""
from __future__ import annotations

import functools
import zlib

# kind -> (mean, standard deviation). GPT-2's and BERT's own initialiser is
# N(0, 0.02); gains and biases get the same spread so that none is an exact 1
# or 0 whose part in the result a broken kernel could drop unseen.
KINDS = {"weight": (0.0, 0.02), "bias": (0.0, 0.02), "gain": (1.0, 0.02)}


def key_of(seed):
    """A threefry key from any whole number up to and beyond 2**31."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32")
    return jax.random.fold_in(key, seed >> 31)


def leaf(key, tag, layer, shape, kind):
    """The float32 value of one leaf. Traceable; `layer` may be traced."""
    import jax
    import jax.numpy as jnp

    mean, std = KINDS[kind]
    k = jax.random.fold_in(key, zlib.crc32(tag.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer)
    return mean + std * jax.random.normal(k, tuple(shape), jnp.float32)


@functools.lru_cache(maxsize=None)
def _leaf_jit():
    import jax

    return jax.jit(leaf, static_argnames=("tag", "shape", "kind"))


def fill(net, leaves, seed):
    """Give every Gluon parameter of `net` its seeded value, made on the
    device by one jitted call per leaf (one program per distinct tag).

    `leaves` is the family's list of ``(gluon name, tag, layer, shape, kind)``;
    a parameter the list does not name, or a shape that differs, is an error."""
    key = key_of(seed)
    make = _leaf_jit()
    params = net.collect_params()
    named = {name for name, *_ in leaves}
    if named != set(params):
        raise ValueError(
            f"seeded leaves do not match the block's parameters: only in the "
            f"block {sorted(set(params) - named)[:4]}, only in the list "
            f"{sorted(named - set(params))[:4]}")
    for name, tag, layer, shape, kind in leaves:
        p = params[name]
        if tuple(p.shape) != tuple(shape):
            raise ValueError(f"{name}: block has {p.shape}, list {shape}")
        p.set_data(make(key, tag=tag, layer=layer, shape=tuple(shape),
                        kind=kind))


def values(leaves, seed):
    """The same leaves again, as ``{gluon name: array}`` (float32)."""
    key = key_of(seed)
    make = _leaf_jit()
    return {name: make(key, tag=tag, layer=layer, shape=tuple(shape),
                       kind=kind)
            for name, tag, layer, shape, kind in leaves}
