"""Flash attention (prefill) — Pallas TPU kernel.

Design (TPU-native, not a CUDA port):
  grid = (B * Hkv * group, Sq/bq, Skv/bk); the last grid dimension is
  "arbitrary" (sequential revisit) so the online-softmax running state
  (m, l, acc) lives in VMEM scratch across kv blocks. Q/K/V blocks are
  VMEM tiles via BlockSpec; block shapes default to (128, 128) × head_dim,
  MXU-aligned (head_dim is 64/80/128 for the assigned archs; the compiler
  pads 80 -> 128 lanes).

Causal + sliding-window masking is block-level: fully-masked kv blocks are
skipped with pl.when (no FLOPs, no HBM traffic beyond the prefetch of the
block — a production version would prune them from the grid), diagonal /
window-edge blocks get an element mask from broadcasted iota.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               bq: int, bk: int, scale: float, causal: bool,
               window: int | None, n_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    # block-level reachability predicate (skips fully-masked kv blocks)
    pred = jnp.asarray(True)
    if causal:
        pred = pred & (k_start <= q_start + bq - 1)
    if window is not None:
        pred = pred & (k_start + bk - 1 > q_start - window)

    @pl.when(pred)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [bq, D]
        k = k_ref[0].astype(jnp.float32)                    # [bk, D]
        v = v_ref[0].astype(jnp.float32)                    # [bk, D]
        s = q @ k.T                                         # [bq, bk]
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask = mask & (cols <= rows)
        if window is not None:
            mask = mask & (cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                 # [bq, 1]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                              # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        denom = l_scr[...]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    window: int | None = None, bq: int = 128, bk: int = 128):
    """q [B, S, H, D]; k, v [B, S, Hkv, D] -> [B, S, H, D]. ``interpret``
    runs the kernel body in the Pallas interpreter (CPU) instead of
    compiling it with Mosaic."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    n_q, n_k = S // bq, S // bk
    scale = 1.0 / (D ** 0.5)

    # [B, S, H, D] -> [B*H, S, D]; kv head for flat q-head j: (j % H) // g
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)

    def q_map(h, iq, ik):
        return (h, iq, 0)

    def kv_map(h, iq, ik):
        return ((h // H) * Hkv + (h % H) // g, ik, 0)

    kernel = functools.partial(_fa_kernel, bq=bq, bk=bk, scale=scale,
                               causal=causal, window=window, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
