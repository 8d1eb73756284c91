"""The parts that the served families' references share, in float32 at the
highest matmul precision, imported from nothing of the program: weight
recipes, pre-LayerNorm attention and MLP blocks, the int8 control's
matmuls, the logit gap, and the shape count of a decoder stack.

Weights follow the recipe a configuration's ``weights`` entry states:
Lecun-normal matrices, embeddings at 0.02, zero biases, unit LayerNorm
gains, in the stage's stated dtype.

``precision="int8"`` is the control: every matmul computed in int8, the
step below bfloat16 that a later change could be tempted to take. Each
operand is rounded to int8 along the contracted axis with one absmax
scale per slice (a weight per output column, an activation per row), and
the products are summed exactly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ weights --

def init_matrix(key, n_in: int, n_out: int, dtype):
    return jax.random.normal(key, (n_in, n_out), dtype=dtype) * (1.0 / n_in ** 0.5)


def init_dense(key, n_in: int, n_out: int, dtype, bias: bool) -> dict:
    p = {"w": init_matrix(key, n_in, n_out, dtype)}
    if bias:
        p["b"] = jnp.zeros((n_out,), dtype)
    return p


def init_layernorm(d: int, dtype) -> dict:
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def init_attention(key, d: int, heads: int, kv_heads: int, head_dim: int, dtype,
                   bias: bool) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {"q": init_dense(kq, d, heads * head_dim, dtype, bias),
            "k": init_dense(kk, d, kv_heads * head_dim, dtype, bias),
            "v": init_dense(kv, d, kv_heads * head_dim, dtype, bias),
            "o": init_dense(ko, heads * head_dim, d, dtype, False)}


def init_mlp(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2, _ = jax.random.split(key, 3)
    return {"up": init_dense(k1, d, d_ff, dtype, True),
            "down": init_dense(k2, d_ff, d, dtype, True)}


def init_embedding(key, rows: int, d: int, dtype):
    return jax.random.normal(key, (rows, d), dtype=dtype) * 0.02


def init_stack(key_emb, key_layers, key_head, n: dict, layer, dtype) -> dict:
    """Token embedding, ``n["layers"]`` layers of ``layer(key)`` stacked
    on a leading axis, a final LayerNorm and an unbiased head."""
    return {"embed": init_embedding(key_emb, n["vocab"], n["d"], dtype),
            "layers": jax.vmap(layer)(jax.random.split(key_layers, n["layers"])),
            "ln_f": init_layernorm(n["d"], dtype),
            "head": init_dense(key_head, n["d"], n["vocab"], dtype, False)}


# ------------------------------------------------------------------ forward --

def int8(x, axis: int):
    """x rounded to int8 along ``axis`` (one absmax scale per slice), back
    in float32: what an int8 matmul operand holds."""
    x = x.astype(F32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    return jnp.clip(jnp.round(x / jnp.where(scale > 0, scale, 1.0)), -127, 127) * scale


def mm(x, w, precision: str):
    """x [..., in] @ w [in, out] -> float32."""
    if precision == "f32":
        return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)
    return jnp.matmul(int8(x, -1), int8(w, 0), precision=HIGHEST)


def act_mm(a, b, spec: str, precision: str):
    """Activation-by-activation product (attention) -> float32."""
    if precision == "f32":
        return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST)
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    (c,) = [ch for ch in sa if ch in sb and ch not in out]
    return jnp.einsum(spec, int8(a, sa.index(c)), int8(b, sb.index(c)),
                      precision=HIGHEST)


def dense(p, x, precision):
    y = mm(x, p["w"], precision)
    if "b" in p:
        y = y + p["b"].astype(F32)
    return y


def layernorm(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"].astype(F32) + p["b"].astype(F32)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, theta: float):
    """Rotate-half rotary positions on x [n, S, heads, hd]."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attend(q, k, v, causal: bool, precision: str):
    """q [n, S, H, hd]; k, v [n, T, Hkv, hd] -> [n, S, H*hd]."""
    n, S, H, hd = q.shape
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = act_mm(q, k, "nshd,nthd->nhst", precision) / math.sqrt(hd)
    if causal:
        keep = jnp.arange(S)[:, None] >= jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = act_mm(p, v, "nhst,nthd->nshd", precision)
    return out.reshape(n, S, H * hd)


def self_attention(p, x, n, rope_theta, precision):
    """Causal grouped-query self-attention; rotary positions where
    ``rope_theta`` is not None."""
    heads, kv = n["heads"], n["kv_heads"]
    hd = n["d"] // heads
    b, S, _ = x.shape
    q = dense(p["q"], x, precision).reshape(b, S, heads, hd)
    k = dense(p["k"], x, precision).reshape(b, S, kv, hd)
    v = dense(p["v"], x, precision).reshape(b, S, kv, hd)
    if rope_theta is not None:
        q, k = rope(q, rope_theta), rope(k, rope_theta)
    return dense(p["o"], attend(q, k, v, True, precision), precision)


def mlp(p, x, precision):
    return dense(p["down"], gelu_tanh(dense(p["up"], x, precision)), precision)


def embed(weights, tokens, n: dict):
    """Token embeddings in float32 for tokens [n, S], taken modulo the
    vocabulary, as the served stage takes them."""
    return weights["embed"][tokens % n["vocab"]].astype(F32)


def head(weights, h, n: dict, precision):
    """Logits [n, S, vocab] from the final LayerNorm and the head."""
    return dense(weights["head"], layernorm(weights["ln_f"], h, n["eps"]), precision)


def logit_gaps(logits, tokens):
    """How far each chosen token's logit lies below the best: [n, S]."""
    chosen = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - chosen


def gap_program(forward, *, precision: str, control: bool):
    """A jitted ``(weights, tokens, served, *inputs) -> gaps`` around a
    family's ``forward(weights, tokens, *inputs, precision=...)``.

    With ``control`` it also returns the gaps of the tokens that the
    ``int8`` forward would put first, read against the float32 logits."""
    def run(weights, tokens, served, *inputs):
        ref = forward(weights, tokens, *inputs, precision=precision)
        gaps = logit_gaps(ref, served)
        if not control:
            return gaps
        low = forward(weights, tokens, *inputs, precision="int8")
        return gaps, logit_gaps(ref, jnp.argmax(low, axis=-1).astype(tokens.dtype))
    return jax.jit(run)


# -------------------------------------------------------------------- cost --

def itemsize(stage: dict) -> int:
    return 2 if stage["torch_dtype"] == "bfloat16" else 4


def stack_cost(n: dict, batch: int, seq: int) -> tuple[float, int, int, int]:
    """What a pre-LayerNorm decoder stack of ``n``'s sizes needs over
    tokens [batch, seq]: (FLOPs, weights per layer, bytes of tokens in and
    out, embedding rows gathered x width).

    FLOPs: 2 per multiply-add of every matmul against a weight, causal
    self-attention over the seq(seq+1)/2 pairs it needs, and the output
    projection; norms, activations and the softmax are not counted."""
    d, heads, L, V, d_ff = n["d"], n["heads"], n["layers"], n["vocab"], n["d_ff"]
    hd = d // heads
    tokens = batch * seq
    pairs = seq * (seq + 1) // 2
    self_attn = d * heads * hd * 2 + d * n["kv_heads"] * hd * 2
    mlp_params = 2 * d * d_ff
    biases = heads * hd + 2 * n["kv_heads"] * hd + d_ff + d
    norms = 2 * 2 * d
    flops = 2.0 * tokens * L * (self_attn + mlp_params)
    flops += 2.0 * 2 * batch * heads * hd * pairs * L       # QK^T and PV
    flops += 2.0 * tokens * d * V                           # output projection
    per_layer = self_attn + mlp_params + biases + norms
    moved = batch * seq * 4 * 2                             # int32 tokens in and out
    gathered = min(V, tokens) * d
    return flops, per_layer, moved, gathered


def stack_bytes(n: dict, per_layer: int, gathered: int, moved: int, item: int) -> float:
    """Every weight once in its stored dtype (the layers, the head and the
    final norm), the rows gathered, and the bytes moved in and out."""
    weights = n["layers"] * per_layer + n["d"] * n["vocab"] + 2 * n["d"]
    return float(weights * item + gathered * item + moved)
