"""The lower precision a control computes in: a matmul input rounded as a
later PR's cheaper arithmetic would round it, per tensor, with the gradient
passed straight through (so the same functions serve a training control)."""
from __future__ import annotations


def fake_int8(x):
    """Round to 255 levels of the tensor's own largest magnitude."""
    import jax
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


ROUND = {"float32": lambda x: x, "int8": fake_int8}
