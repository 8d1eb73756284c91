"""Linear / embedding primitives."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_linear(key, in_dim: int, out_dim: int, *, bias: bool = False,
                dtype=jnp.float32, scale: float | None = None):
    """Lecun-normal weight [in, out] (+ optional zero bias), drawn directly
    in ``dtype`` so a bf16 model never materialises a float32 copy."""
    if scale is None:
        scale = 1.0 / (in_dim ** 0.5)
    w = jax.random.normal(key, (in_dim, out_dim), dtype=dtype) * scale
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype=dtype)
    return p


def linear(params, x):
    y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


def init_embedding(key, vocab: int, dim: int, *, dtype=jnp.float32):
    e = jax.random.normal(key, (vocab, dim), dtype=dtype) * 0.02
    return {"e": e}


def embedding(params, tokens):
    return params["e"][tokens]
