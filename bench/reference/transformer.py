"""The served stages' references under one name, for tests and tools that
import it: each function hands the stage to its family's module
(``bench/reference/<family>.py``, found by ``bench.reference.family``)."""
from __future__ import annotations

from bench.reference import family, whisper_decoder


def dims(stage: dict) -> dict:
    return family(stage).dims(stage)


def init_weights(stage: dict, seed: int):
    return family(stage).init_weights(stage, seed)


def stub_frames(stage: dict, batch: int, row: int):
    return whisper_decoder.stub_frames(stage, batch, row)


def forward(stage: dict, weights, tokens, frames=None, *, precision: str = "f32"):
    """Logits [n, S, vocab] in float32; ``frames`` [n, T, d] for the
    Whisper decoder."""
    inputs = () if frames is None else (frames,)
    return family(stage).forward(stage, weights, tokens, *inputs, precision=precision)


def gap_program(stage: dict, *, precision: str = "f32", control: bool = False):
    """A jitted ``(weights, tokens, served[, frames]) -> gaps``."""
    run = family(stage).gap_program(stage, precision=precision, control=control)

    def call(weights, tokens, served, frames=None):
        return run(weights, tokens, served, *(() if frames is None else (frames,)))
    return call
