"""Family ``whisper_decoder``: Whisper's text decoder (arXiv:2212.04356),
the plain reference, its program view and its shape count.

Learned token and position embeddings, then per layer pre-LayerNorm
causal self-attention, cross-attention over the encoder's frames, and a
GELU MLP; a final LayerNorm and an unbiased output projection. The
encoder is the program's stub: ``stub_encoder`` in the stage states the
frames, which ``reference_inputs`` draws again for each served row.

``forward`` computes in float32 from weights drawn by the stated recipe,
one layer at a time under a scan, so only one layer is ever held in
float32. ``program_view`` is the one function that reads the program:
the sizes it will run, to refuse a configuration file that says
otherwise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import layers


def dims(stage: dict) -> dict:
    """The sizes the stage's configuration states, under the names the
    shared layers use."""
    return {"layers": stage["decoder_layers"], "d": stage["d_model"],
            "heads": stage["decoder_attention_heads"],
            "kv_heads": stage["decoder_attention_heads"],
            "d_ff": stage["decoder_ffn_dim"], "vocab": stage["vocab_size"],
            "positions": stage["max_target_positions"],
            "frames": stage["max_source_positions"],
            "eps": stage["layer_norm_eps"]}


def program_view(arch, stage: dict) -> dict:
    """The stage's sizes as the program will run them, under the
    configuration file's names."""
    from repro.models import whisper
    return {"decoder_layers": arch.n_layers, "d_model": arch.d_model,
            "decoder_attention_heads": arch.n_heads,
            "decoder_ffn_dim": arch.d_ff, "vocab_size": arch.vocab,
            "max_source_positions": arch.enc_len,
            "max_target_positions": whisper.MAX_POSITIONS,
            "torch_dtype": arch.dtype}


def init_weights(stage: dict, seed: int):
    """The stage's weights drawn from ``seed`` in its stated dtype, by one
    jitted program."""
    return jax.jit(lambda key: _init(stage, key))(jax.random.PRNGKey(seed))


def _init(stage: dict, key):
    n = dims(stage)
    dt = jnp.dtype(stage["torch_dtype"])
    d, heads = n["d"], n["heads"]
    hd = d // heads
    k_emb, k_pos, k_layers, k_head = jax.random.split(key, 4)

    def layer(k):
        k_self, k_cross, k_mlp = jax.random.split(k, 3)
        return {"ln_self": layers.init_layernorm(d, dt),
                "self": layers.init_attention(k_self, d, heads, n["kv_heads"], hd, dt, True),
                "ln_cross": layers.init_layernorm(d, dt),
                "cross": layers.init_attention(k_cross, d, heads, heads, hd, dt, True),
                "ln_mlp": layers.init_layernorm(d, dt),
                "mlp": layers.init_mlp(k_mlp, d, n["d_ff"], dt)}

    return {**layers.init_stack(k_emb, k_layers, k_head, n, layer, dt),
            "pos": layers.init_embedding(k_pos, n["positions"], d, dt)}


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


def reference_inputs(stage: dict, batch: int, row: int) -> tuple:
    """What the reference needs beside the tokens for row ``row`` of a
    served batch of ``batch``: the encoder frames that row was given."""
    return (stub_frames(stage, batch, row),)


def _cross_attention(p, x, frames, n, precision):
    heads = n["heads"]
    hd = n["d"] // heads
    b, S, _ = x.shape
    T = frames.shape[1]
    q = layers.dense(p["q"], x, precision).reshape(b, S, heads, hd)
    k = layers.dense(p["k"], frames, precision).reshape(b, T, heads, hd)
    v = layers.dense(p["v"], frames, precision).reshape(b, T, heads, hd)
    return layers.dense(p["o"], layers.attend(q, k, v, False, precision), precision)


def forward(stage: dict, weights, tokens, frames, *, precision: str = "f32"):
    """Logits [n, S, vocab] in float32 for tokens [n, S] over the encoder
    frames [n, T, d]."""
    n = dims(stage)
    ln, eps = layers.layernorm, n["eps"]
    h = layers.embed(weights, tokens, n)
    h = h + weights["pos"][jnp.arange(tokens.shape[1])].astype(layers.F32)[None]
    frames = frames.astype(layers.F32)

    def layer(h, lp):
        h = h + layers.self_attention(lp["self"], ln(lp["ln_self"], h, eps), n, None,
                                      precision)
        h = h + _cross_attention(lp["cross"], ln(lp["ln_cross"], h, eps), frames, n,
                                 precision)
        h = h + layers.mlp(lp["mlp"], ln(lp["ln_mlp"], h, eps), precision)
        return h, None

    h, _ = jax.lax.scan(layer, h, weights["layers"])
    return layers.head(weights, h, n, precision)


def gap_program(stage: dict, *, precision: str = "f32", control: bool = False):
    """A jitted ``(weights, tokens, served, frames) -> gaps`` (and the
    control's gaps with ``control``); see ``layers.gap_program``."""
    return layers.gap_program(partial(forward, stage), precision=precision,
                              control=control)


def forward_cost(stage: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward over [batch, seq] tokens: the decoder
    stack's (``layers.stack_cost``), plus full cross-attention over the
    encoder's frames (its q and o per token, its k and v per frame), the
    cross weights, the frames in, and the position rows gathered."""
    n = dims(stage)
    d, heads, L, T = n["d"], n["heads"], n["layers"], n["frames"]
    hd = d // heads
    item = layers.itemsize(stage)
    flops, per_layer, moved, gathered = layers.stack_cost(n, batch, seq)
    flops += 2.0 * batch * seq * L * 2 * d * d              # cross q and o
    flops += 2.0 * batch * T * L * 2 * d * d                # cross k and v
    flops += 2.0 * 2 * batch * heads * hd * seq * T * L
    per_layer += 4 * d * d + 3 * d + 2 * d                  # cross weights, biases, norm
    moved += batch * T * d * item                           # encoder frames
    gathered += seq * d                                     # position rows
    return flops, layers.stack_bytes(n, per_layer, gathered, moved, item)
