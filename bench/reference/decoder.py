"""Family ``decoder``: a StarCoder2-style decoder (arXiv:2402.19173), the
plain reference, its program view and its shape count.

Token embedding, then per layer pre-LayerNorm grouped-query
self-attention with rotary positions (the rotate-half form) and a GELU
MLP; a final LayerNorm and an unbiased output projection.

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
    return {"layers": stage["num_hidden_layers"], "d": stage["hidden_size"],
            "heads": stage["num_attention_heads"],
            "kv_heads": stage["num_key_value_heads"],
            "d_ff": stage["intermediate_size"], "vocab": stage["vocab_size"],
            "rope_theta": stage["rope_theta"], "eps": stage["norm_epsilon"]}


def program_view(arch, stage: dict) -> dict:
    """The stage's sizes as the program will run them, under the
    configuration file's names."""
    return {"num_hidden_layers": arch.n_layers, "hidden_size": arch.d_model,
            "num_attention_heads": arch.n_heads,
            "num_key_value_heads": arch.n_kv, "intermediate_size": arch.d_ff,
            "vocab_size": arch.vocab, "rope_theta": arch.rope_theta,
            "torch_dtype": arch.dtype}


def init_weights(stage: dict, seed: int):
    """The stage's weights drawn from ``seed`` in its stated dtype, by one
    jitted program."""
    return jax.jit(lambda key: _init(stage, key))(jax.random.PRNGKey(seed))


def _init(stage: dict, key):
    n = dims(stage)
    dt = jnp.dtype(stage["torch_dtype"])
    d, heads = n["d"], n["heads"]
    k_emb, k_layers, k_head, _ = jax.random.split(key, 4)

    def layer(k):
        k_attn, k_mlp = jax.random.split(k)
        return {"ln_self": layers.init_layernorm(d, dt),
                "self": layers.init_attention(k_attn, d, heads, n["kv_heads"],
                                              d // heads, dt, True),
                "ln_mlp": layers.init_layernorm(d, dt),
                "mlp": layers.init_mlp(k_mlp, d, n["d_ff"], dt)}

    return layers.init_stack(k_emb, k_layers, k_head, n, layer, dt)


def reference_inputs(stage: dict, batch: int, row: int) -> tuple:
    """The reference needs nothing beside the tokens."""
    return ()


def forward(stage: dict, weights, tokens, *, precision: str = "f32"):
    """Logits [n, S, vocab] in float32 for tokens [n, S]."""
    n = dims(stage)
    ln, eps = layers.layernorm, n["eps"]
    h = layers.embed(weights, tokens, n)

    def layer(h, lp):
        h = h + layers.self_attention(lp["self"], ln(lp["ln_self"], h, eps), n,
                                      n["rope_theta"], precision)
        h = h + layers.mlp(lp["mlp"], ln(lp["ln_mlp"], h, eps), precision)
        return h, None

    h, _ = jax.lax.scan(layer, h, weights["layers"])
    return layers.head(weights, h, n, precision)


def gap_program(stage: dict, *, precision: str = "f32", control: bool = False):
    """A jitted ``(weights, tokens, served) -> gaps`` (and the control's
    gaps with ``control``); see ``layers.gap_program``."""
    return layers.gap_program(partial(forward, stage), precision=precision,
                              control=control)


def forward_cost(stage: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward over [batch, seq] tokens: the decoder
    stack's (``layers.stack_cost``)."""
    n = dims(stage)
    flops, per_layer, moved, gathered = layers.stack_cost(n, batch, seq)
    return flops, layers.stack_bytes(n, per_layer, gathered, moved,
                                     layers.itemsize(stage))
