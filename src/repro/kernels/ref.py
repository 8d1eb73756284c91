"""Pure-jnp oracles for the Pallas kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int | None = None):
    """q [B, S, H, D]; k, v [B, S, Hkv, D] -> [B, S, H, D] (f32 math)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, S, Hkv, g, D).astype(jnp.float32)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg, k.astype(jnp.float32)) / (D ** 0.5)
    idx = jnp.arange(S)
    mask = jnp.ones((S, S), dtype=bool)
    if causal:
        mask = idx[None, :] <= idx[:, None]
    if window is not None:
        mask = mask & (idx[None, :] > idx[:, None] - window)
    logits = jnp.where(mask[None, None, None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


def decode_attention_ref(q, k, v, lengths):
    """q [B, 1, H, D]; k, v [B, C, Hkv, D]; lengths [B] -> [B, 1, H, D]."""
    B, _, H, D = q.shape
    C = k.shape[1]
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, 1, Hkv, g, D).astype(jnp.float32)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg, k.astype(jnp.float32)) / (D ** 0.5)
    valid = jnp.arange(C)[None, :] < lengths[:, None]           # [B, C]
    mask = valid[:, None, None, None, :]
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)
