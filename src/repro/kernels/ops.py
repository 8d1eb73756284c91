"""Public jit'd wrappers for the Pallas kernels.

The one place that decides how the kernels run: on a CPU backend (tests)
the kernel body executes in the Pallas interpreter with real dataflow,
validated against ref.py; on any other backend the same pallas_call is
compiled by Mosaic.
"""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    bq: int = 128, bk: int = 128):
    """q [B, S, H, D]; k, v [B, S, Hkv, D] -> [B, S, H, D]."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=_interpret())


def decode_attention(q, k, v, lengths, *, bk: int = 512):
    """q [B, 1, H, D]; k, v [B, C, Hkv, D]; lengths [B] -> [B, 1, H, D]."""
    return _da.decode_attention(q, k, v, lengths, bk=bk,
                                interpret=_interpret())
