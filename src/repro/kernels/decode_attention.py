"""GQA single-token decode attention — Pallas TPU kernel.

The decode hot loop of decode_32k / long_500k: one query token per sequence
against a long KV cache. This is memory-bound (arithmetic intensity ~ group
size), so the kernel is organised to stream K/V through VMEM exactly once:

  grid = (B, Hkv, C/bk), last dim sequential with online-softmax scratch.
  Per program: q tile [group, D] (all query heads of one kv head — the
  GQA group is folded into the matmul M dimension so the MXU tile is
  [group, D] x [D, bk] instead of a degenerate [1, D] GEMV).

Validity is a per-sequence length: slots [0, length) hold keys, for
contiguous caches and ring-buffer window caches alike (a ring's filled
slots are its first min(pos + 1, C)). The lengths ride in SMEM as a
scalar-prefetch operand, so no mask tile has to meet the (8, 128) VMEM
block tiling, and kv blocks wholly past a sequence's length are skipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, bk: int, n_k: int):
    bh = pl.program_id(0)
    ik = pl.program_id(2)
    length = len_ref[bh]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ik * bk < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [g, D]
        k = k_ref[0].astype(jnp.float32)                  # [bk, D]
        v = v_ref[0].astype(jnp.float32)                  # [bk, D]
        s = q @ k.T                                       # [g, bk]
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        denom = l_scr[...]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k, v, lengths, *, interpret: bool, bk: int = 512):
    """q [B, 1, H, D]; k, v [B, C, Hkv, D]; lengths [B] (valid slots
    [0, length)) -> [B, 1, H, D]. ``interpret`` runs the kernel body in
    the Pallas interpreter (CPU) instead of compiling it with Mosaic."""
    B, _, H, D = q.shape
    C = k.shape[1]
    Hkv = k.shape[2]
    g = H // Hkv
    bk = min(bk, C)
    assert C % bk == 0
    n_k = C // bk
    scale = 1.0 / (D ** 0.5)

    # q -> [B*Hkv, g, D]; kv -> [B*Hkv, C, D]
    qf = q[:, 0].reshape(B, Hkv, g, D).reshape(B * Hkv, g, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, C, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, C, D)
    lens = jnp.repeat(lengths.astype(jnp.int32), Hkv)  # [B*Hkv]

    def q_map(bh, _h, ik, _lens):
        return (bh, 0, 0)

    def kv_map(bh, _h, ik, _lens):
        return (bh, ik, 0)

    kernel = functools.partial(_dec_kernel, scale=scale, bk=bk, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hkv, 1, n_k),
            in_specs=[
                pl.BlockSpec((1, g, D), q_map),
                pl.BlockSpec((1, bk, D), kv_map),
                pl.BlockSpec((1, bk, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, g, D), q_map),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, g, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(B, Hkv * g, D)[:, None].reshape(B, 1, H, D)
