"""Operations and bytes that one served forward needs, counted from shapes.

Each served family counts its own (``forward_cost`` of
``bench/reference/<family>.py``; the count of the layer stack that the
families share is ``bench/reference/layers.py:stack_cost``):
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

from bench.reference import family

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def forward_cost(stage: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward of ``stage`` over [batch, seq] tokens,
    as the stage's family counts them."""
    return family(stage).forward_cost(stage, batch, seq)


def roofline_s(stage: dict, batch: int, seq: int, peak: dict) -> tuple[float, str]:
    """Least seconds for one forward on a chip with ``peak``, and its bound."""
    flops, nbytes = forward_cost(stage, batch, seq)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes else "memory")
