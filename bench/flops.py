"""Operations and bytes that one served forward needs, counted from shapes.

A forward over tokens [B, S] is charged:
- FLOPs: 2 per multiply-add of every matmul against a weight, the
  attention products (causal self-attention over the S(S+1)/2 pairs it
  needs, full cross-attention over the encoder's frames) and the output
  projection. Norms, activations and the softmax are not counted.
- bytes: every weight once in its stored dtype, the embedding rows the
  batch gathers, the tokens in, the encoder frames in, and the chosen
  tokens out (int32 each). Intermediate activations are not counted: a
  program that fused everything would not move them.

``roofline_s`` is the least time a chip with the given peaks needs for a
forward: the larger of FLOPs over peak FLOP/s and bytes over peak
bandwidth; ``bound`` says which of the two it is.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench.reference.transformer import dims

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def _attention_params(d: int, heads: int, kv_heads: int) -> int:
    hd = d // heads
    return d * heads * hd * 2 + d * kv_heads * hd * 2


def forward_cost(stage: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward of ``stage`` over [batch, seq] tokens."""
    n = dims(stage)
    d, heads, L, V, d_ff = n["d"], n["heads"], n["layers"], n["vocab"], n["d_ff"]
    hd = d // heads
    item = 2 if stage["torch_dtype"] == "bfloat16" else 4
    tokens = batch * seq
    pairs = seq * (seq + 1) // 2
    self_attn = _attention_params(d, heads, n["kv_heads"])
    mlp = 2 * d * d_ff
    biases = heads * hd + 2 * n["kv_heads"] * hd + d_ff + d
    norms = 2 * 2 * d
    flops = 2.0 * tokens * L * (self_attn + mlp)
    flops += 2.0 * 2 * batch * heads * hd * pairs * L       # QK^T and PV
    flops += 2.0 * tokens * d * V                           # output projection
    per_layer = self_attn + mlp + biases + norms
    moved = batch * seq * 4 * 2                             # tokens in and out
    gathered = min(V, tokens) * d
    if stage["family"] == "whisper_decoder":
        T = n["frames"]
        flops += 2.0 * tokens * L * 2 * d * d               # cross q and o
        flops += 2.0 * batch * T * L * 2 * d * d            # cross k and v
        flops += 2.0 * 2 * batch * heads * hd * seq * T * L
        per_layer += 4 * d * d + 3 * d + norms // 2
        moved += batch * T * d * item                       # encoder frames
        gathered += seq * d                                 # position rows
    weights = L * per_layer + d * V + 2 * d
    return flops, float(weights * item + gathered * item + moved)


def roofline_s(stage: dict, batch: int, seq: int, peak: dict) -> tuple[float, str]:
    """Least seconds for one forward on a chip with ``peak``, and its bound."""
    flops, nbytes = forward_cost(stage, batch, seq)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes else "memory")
