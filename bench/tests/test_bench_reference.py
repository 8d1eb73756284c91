"""The reference that decides ``correct`` for a served cell, on the CPU at
smoke widths: it draws the program's weights without taking them, agrees
with the served forward, reads the int8 control above a limit,
and the run comes out not correct when the timed path is broken."""
from __future__ import annotations

import importlib.util
import json
import sys
import time

import jax
import numpy as np
import pytest

import bench_smoke

REPO = bench_smoke.REPO
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness  # noqa: E402
from bench.reference import transformer as ref  # noqa: E402

CONFIG = json.loads((REPO / "bench" / "configs" / "speech2code.json").read_text())


def _calibrate():
    spec = importlib.util.spec_from_file_location(
        "bench_calibrate", REPO / "bench" / "tools" / "calibrate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _smoke_stage(i: int, dtype: str) -> dict:
    return {**CONFIG["stages"][i], **bench_smoke.SMOKE_STAGES[i], "torch_dtype": dtype}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("i", [0, 1])
def test_reference_draws_the_programs_weights(i, dtype):
    from repro.configs import ARCHS
    from repro.models import api as models
    stage = _smoke_stage(i, dtype)
    arch = ARCHS[stage["model"]].smoke().replace(dtype=dtype)
    program = jax.jit(lambda k: models.init_model(k, arch))(jax.random.PRNGKey(2024))
    assert _calibrate().weight_mismatch(stage, program, 2024) == 0.0


@pytest.mark.parametrize("i", [0, 1])
def test_reference_forward_agrees_with_the_served_forward(i):
    from repro.configs import ARCHS
    from repro.serving.engine import StageServer
    stage = _smoke_stage(i, "float32")
    srv = StageServer("s", [ARCHS[stage["model"]].smoke()], seq_len=16, seed=5)
    tokens = np.random.default_rng(0).integers(1, 500, (3, 16)).astype(np.int32)
    served = srv.execute(0, tokens)
    frames = None
    if stage["family"] == "whisper_decoder":
        frames = np.stack([np.asarray(ref.stub_frames(stage, 3, r)) for r in range(3)])
    weights = ref.init_weights(stage, 5)
    gaps = np.asarray(ref.gap_program(stage)(weights, tokens, served, frames))
    assert gaps.max() <= 1e-4
    logits = np.asarray(ref.forward(stage, weights, tokens, frames))
    from repro.models import api as models
    batch = srv._make_batch(tokens, srv.cfg)
    program_logits, _ = models.forward(srv.weights(0), batch, srv.cfg)
    np.testing.assert_allclose(logits, np.asarray(program_logits), rtol=2e-3, atol=2e-3)


def _run(root, *, control=False):
    cell = harness.resolve(bench_smoke.CELL, root=root)
    args = harness.Args(bench_smoke.CELL, 3_000_000_017, 1.0, False, control)
    return harness.driver(cell).run(cell, args, t0=time.perf_counter())


def test_control_reads_above_the_limit_where_the_program_reads_below(tmp_path):
    result = _run(bench_smoke.make(tmp_path), control=True)
    assert result.correct
    for i, (low, low_mean) in enumerate(zip(result.context["control_gap"],
                                            result.context["control_gap_mean"])):
        value, limit = result.checks[f"logit_gap.stage{i}"]
        mean, mean_limit = result.checks[f"logit_gap_mean.stage{i}"]
        assert value <= limit and mean <= mean_limit
        assert low > limit or low_mean > mean_limit


def _alter_token(out, tokens):
    out = out.copy()
    out[:, 0] = (out[:, 0] + 1) % 512
    return out


def _half_batch(out, tokens):
    out = out.copy()
    half = out.shape[0] // 2
    out[half:] = tokens[half:]
    return out


@pytest.mark.parametrize("fault", ["token_altered", "half_batch_left_out",
                                   "answers_misrouted"])
def test_a_broken_timed_path_is_not_correct(fault, tmp_path, monkeypatch):
    from repro.serving import engine, runtime
    if fault == "answers_misrouted":
        stack = runtime.stack_tokens
        monkeypatch.setattr(runtime, "stack_tokens", lambda reqs, s: stack(reqs[::-1], s))
    else:
        broken = {"token_altered": _alter_token, "half_batch_left_out": _half_batch}[fault]
        execute = engine.StageServer.execute

        def call(self, z, tokens):
            return broken(np.asarray(execute(self, z, tokens)), tokens)
        monkeypatch.setattr(engine.StageServer, "__call__", call)
    result = _run(bench_smoke.make(tmp_path))
    assert not result.correct
    assert result.failed > 0
