"""Plain reference for the served stage models, in float32 at the highest
matmul precision, written from the published descriptions and imported
from nothing of the program.

Two families, as a configuration file's stage names them:

- ``whisper_decoder``: Whisper's text decoder (arXiv:2212.04356). Learned
  token and position embeddings, then per layer pre-LayerNorm causal
  self-attention, cross-attention over the encoder's frames, and a GELU
  MLP; a final LayerNorm and an unbiased output projection.
- ``decoder``: a StarCoder2-style decoder (arXiv:2402.19173). Token
  embedding, then per layer pre-LayerNorm grouped-query self-attention
  with rotary positions (the rotate-half form) and a GELU MLP; a final
  LayerNorm and an unbiased output projection.

Weights are drawn from the run's seed by the recipe a stage's
``weights`` entry states (Lecun-normal matrices, embeddings at 0.02,
zero biases, unit LayerNorm gains), in the stage's stated dtype, so the
reference holds the same numbers as the served program without taking
any from it. ``forward`` computes in float32 from those values, one layer
at a time under a scan, so only one layer is ever held in float32.

``precision="int8"`` is the control: the same forward with every matmul
computed in int8, the step below bfloat16 that a later change could be
tempted to take. Each operand is rounded to int8 along the contracted
axis with one absmax scale per slice (a weight per output column, an
activation per row), and the products are summed exactly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ weights --

def _matrix(key, n_in: int, n_out: int, dtype):
    return jax.random.normal(key, (n_in, n_out), dtype=dtype) * (1.0 / n_in ** 0.5)


def _dense(key, n_in: int, n_out: int, dtype, bias: bool) -> dict:
    p = {"w": _matrix(key, n_in, n_out, dtype)}
    if bias:
        p["b"] = jnp.zeros((n_out,), dtype)
    return p


def _layernorm(d: int, dtype) -> dict:
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def _attention(key, d: int, heads: int, kv_heads: int, head_dim: int, dtype,
               bias: bool) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {"q": _dense(kq, d, heads * head_dim, dtype, bias),
            "k": _dense(kk, d, kv_heads * head_dim, dtype, bias),
            "v": _dense(kv, d, kv_heads * head_dim, dtype, bias),
            "o": _dense(ko, heads * head_dim, d, dtype, False)}


def _mlp(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2, _ = jax.random.split(key, 3)
    return {"up": _dense(k1, d, d_ff, dtype, True),
            "down": _dense(k2, d_ff, d, dtype, True)}


def _embedding(key, rows: int, d: int, dtype):
    return jax.random.normal(key, (rows, d), dtype=dtype) * 0.02


def dims(stage: dict) -> dict:
    """The sizes a stage's configuration states, under one set of names."""
    if stage["family"] == "whisper_decoder":
        return {"layers": stage["decoder_layers"], "d": stage["d_model"],
                "heads": stage["decoder_attention_heads"],
                "kv_heads": stage["decoder_attention_heads"],
                "d_ff": stage["decoder_ffn_dim"], "vocab": stage["vocab_size"],
                "positions": stage["max_target_positions"],
                "frames": stage["max_source_positions"],
                "eps": stage["layer_norm_eps"]}
    if stage["family"] == "decoder":
        return {"layers": stage["num_hidden_layers"], "d": stage["hidden_size"],
                "heads": stage["num_attention_heads"],
                "kv_heads": stage["num_key_value_heads"],
                "d_ff": stage["intermediate_size"], "vocab": stage["vocab_size"],
                "rope_theta": stage["rope_theta"], "eps": stage["norm_epsilon"]}
    raise ValueError(f"no reference for family {stage['family']!r}")


def init_weights(stage: dict, seed: int):
    """The stage's weights drawn from ``seed`` in its stated dtype, by one
    jitted program."""
    return jax.jit(lambda key: _init(stage, key))(jax.random.PRNGKey(seed))


def _init(stage: dict, key):
    n = dims(stage)
    dt = jnp.dtype(stage["torch_dtype"])
    d, heads, kv = n["d"], n["heads"], n["kv_heads"]
    hd = d // heads
    if stage["family"] == "whisper_decoder":
        k_emb, k_pos, k_layers, k_head = jax.random.split(key, 4)

        def layer(k):
            k_self, k_cross, k_mlp = jax.random.split(k, 3)
            return {"ln_self": _layernorm(d, dt),
                    "self": _attention(k_self, d, heads, kv, hd, dt, True),
                    "ln_cross": _layernorm(d, dt),
                    "cross": _attention(k_cross, d, heads, heads, hd, dt, True),
                    "ln_mlp": _layernorm(d, dt),
                    "mlp": _mlp(k_mlp, d, n["d_ff"], dt)}

        extra = {"pos": _embedding(k_pos, n["positions"], d, dt)}
    else:
        k_emb, k_layers, k_head, _ = jax.random.split(key, 4)

        def layer(k):
            k_attn, k_mlp = jax.random.split(k)
            return {"ln_self": _layernorm(d, dt),
                    "self": _attention(k_attn, d, heads, kv, hd, dt, True),
                    "ln_mlp": _layernorm(d, dt),
                    "mlp": _mlp(k_mlp, d, n["d_ff"], dt)}

        extra = {}
    return {"embed": _embedding(k_emb, n["vocab"], d, dt),
            "layers": jax.vmap(layer)(jax.random.split(k_layers, n["layers"])),
            "ln_f": _layernorm(d, dt),
            "head": _dense(k_head, d, n["vocab"], dt, False),
            **extra}


def stub_frames(stage: dict, batch: int, row: int):
    """Row ``row`` of the stub encoder's frames for a batch of ``batch``
    requests, as the stage's ``stub_encoder`` entry states them: normal
    draws of shape [batch, frames, d] from a fixed key, times a scale, in
    the stated dtype. Computed op by op, as the served path does."""
    st = stage["stub_encoder"]
    n = dims(stage)
    dt = jnp.dtype(stage["torch_dtype"])
    x = jax.random.normal(jax.random.PRNGKey(st["key"]),
                          (batch, n["frames"], n["d"]), dt) * st["scale"]
    return x[row]


# ------------------------------------------------------------------ forward --

def _int8(x, axis: int):
    """x rounded to int8 along ``axis`` (one absmax scale per slice), back
    in float32: what an int8 matmul operand holds."""
    x = x.astype(F32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    return jnp.clip(jnp.round(x / jnp.where(scale > 0, scale, 1.0)), -127, 127) * scale


def _mm(x, w, precision: str):
    """x [..., in] @ w [in, out] -> float32."""
    if precision == "f32":
        return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)
    return jnp.matmul(_int8(x, -1), _int8(w, 0), precision=HIGHEST)


def _act_mm(a, b, spec: str, precision: str):
    """Activation-by-activation product (attention) -> float32."""
    if precision == "f32":
        return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST)
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    (c,) = [ch for ch in sa if ch in sb and ch not in out]
    return jnp.einsum(spec, _int8(a, sa.index(c)), _int8(b, sb.index(c)),
                      precision=HIGHEST)


def _dense_apply(p, x, precision):
    y = _mm(x, p["w"], precision)
    if "b" in p:
        y = y + p["b"].astype(F32)
    return y


def _ln(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"].astype(F32) + p["b"].astype(F32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x, theta: float):
    """Rotate-half rotary positions on x [n, S, heads, hd]."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend(q, k, v, causal: bool, precision: str):
    """q [n, S, H, hd]; k, v [n, T, Hkv, hd] -> [n, S, H*hd]."""
    n, S, H, hd = q.shape
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = _act_mm(q, k, "nshd,nthd->nhst", precision) / math.sqrt(hd)
    if causal:
        keep = jnp.arange(S)[:, None] >= jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = _act_mm(p, v, "nhst,nthd->nshd", precision)
    return out.reshape(n, S, H * hd)


def _self_attention(p, x, n, rope_theta, precision):
    heads, kv = n["heads"], n["kv_heads"]
    hd = n["d"] // heads
    b, S, _ = x.shape
    q = _dense_apply(p["q"], x, precision).reshape(b, S, heads, hd)
    k = _dense_apply(p["k"], x, precision).reshape(b, S, kv, hd)
    v = _dense_apply(p["v"], x, precision).reshape(b, S, kv, hd)
    if rope_theta is not None:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    return _dense_apply(p["o"], _attend(q, k, v, True, precision), precision)


def _cross_attention(p, x, frames, n, precision):
    heads = n["heads"]
    hd = n["d"] // heads
    b, S, _ = x.shape
    T = frames.shape[1]
    q = _dense_apply(p["q"], x, precision).reshape(b, S, heads, hd)
    k = _dense_apply(p["k"], frames, precision).reshape(b, T, heads, hd)
    v = _dense_apply(p["v"], frames, precision).reshape(b, T, heads, hd)
    return _dense_apply(p["o"], _attend(q, k, v, False, precision), precision)


def _mlp_apply(p, x, precision):
    return _dense_apply(p["down"], _gelu_tanh(_dense_apply(p["up"], x, precision)),
                        precision)


def forward(stage: dict, weights, tokens, frames=None, *, precision: str = "f32"):
    """Logits [n, S, vocab] in float32 for tokens [n, S] (taken modulo the
    vocabulary, as the served stage takes them); ``frames`` [n, T, d] for
    the Whisper decoder."""
    n = dims(stage)
    whisper = stage["family"] == "whisper_decoder"
    rope = None if whisper else n["rope_theta"]
    ids = tokens % n["vocab"]
    h = weights["embed"][ids].astype(F32)
    if whisper:
        h = h + weights["pos"][jnp.arange(tokens.shape[1])].astype(F32)[None]
        frames = frames.astype(F32)

    def layer(h, lp):
        h = h + _self_attention(lp["self"], _ln(lp["ln_self"], h, n["eps"]), n,
                                rope, precision)
        if whisper:
            h = h + _cross_attention(lp["cross"], _ln(lp["ln_cross"], h, n["eps"]),
                                     frames, n, precision)
        h = h + _mlp_apply(lp["mlp"], _ln(lp["ln_mlp"], h, n["eps"]), precision)
        return h, None

    h, _ = jax.lax.scan(layer, h, weights["layers"])
    return _dense_apply(weights["head"], _ln(weights["ln_f"], h, n["eps"]), precision)


def logit_gaps(logits, tokens):
    """How far each chosen token's logit lies below the best: [n, S]."""
    chosen = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - chosen


def gap_program(stage: dict, *, precision: str = "f32", control: bool = False):
    """A jitted ``(weights, tokens, served[, frames]) -> gaps`` for one stage.

    With ``control`` it also returns the gaps of the tokens that the
    ``int8`` forward would put first, read against the float32 logits."""
    def run(weights, tokens, served, frames=None):
        ref = forward(stage, weights, tokens, frames, precision=precision)
        gaps = logit_gaps(ref, served)
        if not control:
            return gaps
        low = forward(stage, weights, tokens, frames, precision="int8")
        return gaps, logit_gaps(ref, jnp.argmax(low, axis=-1).astype(tokens.dtype))
    return jax.jit(run)
