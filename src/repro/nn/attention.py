"""Grouped-query attention: prefill (full-causal or sliding-window) and
single-token decode against a KV cache (contiguous or ring-buffer window).

Shapes:
    x           [B, S, d_model]
    q           [B, S, n_heads, head_dim]
    k/v         [B, S, n_kv, head_dim]
    cache k/v   [B, C, n_kv, head_dim]  (C = max context or window size)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.linear import init_linear, linear
from repro.nn.rope import apply_rope, rope_frequencies


def init_attention(key, dim: int, n_heads: int, n_kv: int, head_dim: int,
                   *, dtype=jnp.float32, qkv_bias: bool = False):
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], dim, n_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wk": init_linear(ks[1], dim, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
        "wv": init_linear(ks[2], dim, n_kv * head_dim, bias=qkv_bias, dtype=dtype),
        "wo": init_linear(ks[3], n_heads * head_dim, dim, dtype=dtype),
    }


def _qkv(params, x, n_heads: int, n_kv: int, head_dim: int):
    B, S, _ = x.shape
    q = linear(params["wq"], x).reshape(B, S, n_heads, head_dim)
    k = linear(params["wk"], x).reshape(B, S, n_kv, head_dim)
    v = linear(params["wv"], x).reshape(B, S, n_kv, head_dim)
    return q, k, v


def _sdpa(q, k, v, mask):
    """q [B,S,H,D]; k,v [B,T,Hkv,D]; mask [S,T] or [B,S,T] additive(-inf) bool=keep."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, S, Hkv, group, D)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / (D ** 0.5)
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(mask, logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


_NEG = -1e30            # finite -inf stand-in: keeps online-softmax grads NaN-free


def _sdpa_blocked(q, k, v, *, window=None, kv_chunk: int = 1024):
    """Causal GQA attention without the [S, S] tensor: a lax.scan over KV
    chunks carries the online-softmax state (m, l, acc) — the flash pattern
    in pure jnp, so long prefills stream O(S·chunk) instead of O(S²).
    q [B,S,H,D]; k,v [B,T,Hkv,D] -> [B,S,H,D]."""
    B, S, H, D = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    g = H // Hkv
    chunk = min(kv_chunk, T)
    assert T % chunk == 0, (T, chunk)
    nb = T // chunk
    # heads stay FLAT on the H axis (sharding-friendly — a [B,S,Hkv,g,D]
    # reshape would break the "model"-axis head sharding and every device
    # would compute all H heads); the small per-chunk KV block is repeated
    # to H instead (g-fold, ~MBs).
    qf = q.astype(jnp.float32) / (D ** 0.5)
    kc = jnp.moveaxis(k.reshape(B, nb, chunk, Hkv, D), 1, 0).astype(jnp.float32)
    vc = jnp.moveaxis(v.reshape(B, nb, chunk, Hkv, D), 1, 0).astype(jnp.float32)
    iq = jnp.arange(S)

    def body(carry, inp):
        m, lsum, acc = carry                    # [B,S,H] / [B,S,H] / [..,D]
        k_k, v_k, j0 = inp                      # [B,chunk,Hkv,D]
        kr = jnp.repeat(k_k, g, axis=2)         # [B,chunk,H,D]
        vr = jnp.repeat(v_k, g, axis=2)
        logits = jnp.einsum("bshd,bchd->bshc", qf, kr)        # [B,S,H,C]
        jk = j0 + jnp.arange(chunk)
        keep = jk[None, :] <= iq[:, None]                     # causal
        if window is not None:
            keep &= jk[None, :] > iq[:, None] - window
        logits = jnp.where(keep[None, :, None, :], logits, _NEG)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        scale = jnp.exp(m - m_new)
        lsum = lsum * scale + p.sum(axis=-1)
        acc = acc * scale[..., None] + jnp.einsum("bshc,bchd->bshd", p, vr)
        return (m_new, lsum, acc), None

    m0 = jnp.full((B, S, H), _NEG, jnp.float32)
    l0 = jnp.zeros((B, S, H), jnp.float32)
    a0 = jnp.zeros((B, S, H, D), jnp.float32)
    offs = jnp.arange(nb) * chunk
    (m, lsum, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, offs))
    out = acc / jnp.maximum(lsum, 1e-30)[..., None]
    return out.astype(q.dtype)


def attention_prefill(params, x, *, n_heads: int, n_kv: int, head_dim: int,
                      rope_theta: float | None = 10000.0, window: int | None = None,
                      positions=None, use_flash: bool = False,
                      blocked_threshold: int = 4096):
    """Causal self-attention over a full sequence. Returns (out, (k, v)).
    Sequences longer than ``blocked_threshold`` stream through the blocked
    online-softmax path (no [S, S] materialisation)."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, x, n_heads, n_kv, head_dim)
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    if rope_theta is not None:
        inv = rope_frequencies(head_dim, theta=rope_theta)
        q = apply_rope(q, positions, inv)
        k = apply_rope(k, positions, inv)
    if use_flash:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, window=window)
    elif S > blocked_threshold and S % 1024 == 0:
        out = _sdpa_blocked(q, k, v, window=window)
    else:
        idx = jnp.arange(S)
        mask = idx[None, :] <= idx[:, None]            # causal
        if window is not None:
            mask = mask & (idx[None, :] > idx[:, None] - window)
        out = _sdpa(q, k, v, mask[None, None, None, :, :])
    out = out.reshape(B, S, n_heads * head_dim)
    return linear(params["wo"], out), (k, v)


def make_kv_cache(batch: int, context: int, n_kv: int, head_dim: int, *, dtype=jnp.float32):
    sh = (batch, context, n_kv, head_dim)
    return {"k": jnp.zeros(sh, dtype=dtype), "v": jnp.zeros(sh, dtype=dtype),
            "pos": jnp.zeros((batch,), dtype=jnp.int32)}


def attention_decode(params, x, cache, *, n_heads: int, n_kv: int, head_dim: int,
                     rope_theta: float | None = 10000.0, ring: bool = False,
                     use_flash: bool = False):
    """One-token decode. x [B, 1, d]. cache entries [B, C, kv, hd].

    ``ring=True`` treats the cache as a sliding-window ring buffer (writes wrap);
    otherwise positions index the cache contiguously. Returns (out, new_cache).
    """
    B, S, _ = x.shape
    assert S == 1
    C = cache["k"].shape[1]
    pos = cache["pos"]                                   # [B]
    q, k, v = _qkv(params, x, n_heads, n_kv, head_dim)
    if rope_theta is not None:
        inv = rope_frequencies(head_dim, theta=rope_theta)
        q = apply_rope(q, pos[:, None], inv)
        k = apply_rope(k, pos[:, None], inv)
    slot = (pos % C) if ring else jnp.minimum(pos, C - 1)
    bidx = jnp.arange(B)
    # write in CACHE dtype: rope returns f32, and .at[].set would otherwise
    # promote the whole [B, C, kv, hd] buffer to f32 (2x HBM + converts)
    new_k = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))  # reprolint: ignore[RPL005] canonical decode-path KV slot write, not vmapped over the cache
    new_v = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))  # reprolint: ignore[RPL005] canonical decode-path KV slot write, not vmapped over the cache
    # valid slots: contiguous -> [0, pos]; ring -> min(pos+1, C) most recent
    n_valid = jnp.minimum(pos + 1, C)                    # [B]
    if use_flash:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, new_k, new_v, n_valid)
    else:
        mask = jnp.arange(C)[None, :] < n_valid[:, None]     # [B, C]
        out = _sdpa(q, new_k, new_v, mask[:, None, None, None, :])
    out = out.reshape(B, 1, n_heads * head_dim)
    new_cache = {"k": new_k, "v": new_v, "pos": pos + 1}
    return linear(params["wo"], out), new_cache


def init_cross_attention(key, dim: int, n_heads: int, head_dim: int, *, dtype=jnp.float32):
    return init_attention(key, dim, n_heads, n_heads, head_dim, dtype=dtype, qkv_bias=True)


def cross_attention(params, x, enc, *, n_heads: int, head_dim: int):
    """x [B,S,d] attends to encoder states enc [B,T,d] (no mask, no rope)."""
    B, S, _ = x.shape
    T = enc.shape[1]
    q = linear(params["wq"], x).reshape(B, S, n_heads, head_dim)
    k = linear(params["wk"], enc).reshape(B, T, n_heads, head_dim)
    v = linear(params["wv"], enc).reshape(B, T, n_heads, head_dim)
    mask = jnp.ones((1, 1, 1, S, T), dtype=bool)
    out = _sdpa(q, k, v, mask).reshape(B, S, n_heads * head_dim)
    return linear(params["wo"], out)
