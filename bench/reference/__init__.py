"""Plain references that decide ``correct``, written from the published
descriptions and imported from nothing of the program.

A served stage's model family is a file: ``bench/reference/<family>.py``
for the ``family`` string of a stage in a configuration file. It
exposes the five functions of ``FUNCTIONS``:

- ``program_view(arch, stage) -> dict``: the sizes as the program runs
  them, under the configuration file's names;
- ``init_weights(stage, seed)``: the weights, drawn from the seed by the
  configuration's recipe;
- ``reference_inputs(stage, batch, row) -> tuple``: what the reference
  needs beside the tokens for one row of a served batch;
- ``gap_program(stage, *, precision="f32", control=False)``: a jitted
  ``(weights, tokens, served, *inputs) -> gaps``;
- ``forward_cost(stage, batch, seq) -> (flops, bytes)``: one forward's
  work, counted from shapes.

``layers.py`` holds what the families share; ``ppo.py`` and ``twin.py``
are the training cell's references.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FUNCTIONS = ("program_view", "init_weights", "reference_inputs", "gap_program",
             "forward_cost")


def family(stage: dict, root: Path | None = None):
    """The module of the stage's family. With ``root``, it is imported by
    path from ``root``'s ``bench/reference/<family>.py``; without, the
    one imported last under that name, else the one beside this file.
    Stops, naming the file, where it is missing or lacks a function."""
    name = stage["family"]
    key = f"bench_family_{name}"
    if root is None and key in sys.modules:
        return sys.modules[key]
    path = (ROOT if root is None else Path(root)) / "bench" / "reference" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: stage {stage.get('model')!r} is of family {name!r}, "
                         f"and no file {path} holds its reference")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in FUNCTIONS if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"bench: {path} lacks {', '.join(missing)}")
    sys.modules[key] = mod
    return mod
