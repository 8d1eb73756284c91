"""A served stage's model family is a file of its own: a copy of the
benchmark that names a family kept only in the copy serves a correct
cell; a family with no file stops the command before any weights are
drawn; the families' shape counts keep their numbers. Beside them, what
the serve driver leaves in a result's context for later per-layer
metrics: every operation's device time, and the stage servers' counters
on either side of the window."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import bench_smoke

REPO = bench_smoke.REPO
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import flops, harness, reference, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
STAGES = json.loads((REPO / "bench" / "configs" / "speech2code.json").read_text())["stages"]
SMOKE_CONFIG = Path("bench") / "configs" / "speech2code_smoke.json"


def _name_family(root: Path, stage: int, family: str) -> dict:
    """The copy's smoke configuration with stage ``stage`` of ``family``."""
    path = root / SMOKE_CONFIG
    cfg = json.loads(path.read_text())
    cfg["stages"][stage]["family"] = family
    path.write_text(json.dumps(cfg))
    return cfg["stages"][stage]


def _run(root: Path) -> tuple[harness.Cell, harness.Result]:
    cell = harness.resolve(bench_smoke.CELL, root=root)
    args = harness.Args(bench_smoke.CELL, 2**31 + 4111, 1.0, False)
    return cell, harness.driver(cell).run(cell, args, t0=time.perf_counter())


def test_a_family_added_as_a_file_serves_a_correct_cell(tmp_path):
    root = bench_smoke.make(tmp_path)
    (root / "bench" / "reference" / "code_decoder.py").write_text(
        '"""Family ``code_decoder``: the ``decoder`` family under another name."""\n'
        "from bench.reference.decoder import (  # noqa: F401\n"
        "    forward_cost, gap_program, init_weights, program_view, reference_inputs)\n")
    assert not (REPO / "bench" / "reference" / "code_decoder.py").exists()
    stage = _name_family(root, 1, "code_decoder")
    cell, result = _run(root)
    line = harness.result_line(cell, result, False)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert {"logit_gap.stage1", "logit_gap_mean.stage1"} <= set(line["checks"])
    assert Path(reference.family(stage).__file__).parent == root / "bench" / "reference"
    # the per-layer metrics' cost count finds the family the driver found
    plain = {**stage, "family": "decoder"}
    assert flops.forward_cost(stage, 2, 32) == flops.forward_cost(plain, 2, 32)


def test_a_family_without_a_file_stops_the_command_before_weights_are_drawn(tmp_path):
    root = bench_smoke.make(tmp_path)
    _name_family(root, 1, "no_such_family")
    # the command as the chip runs it, with the device check passed and
    # the program's weight draw made to announce itself
    code = ("import runpy, sys\n"
            "from bench import harness\n"
            "from repro.serving import engine\n"
            "harness.check_device = lambda chips: {'platform': 'tpu', 'kind': 'test', "
            "'count': chips}\n"
            "def drawn(self, z):\n"
            "    raise RuntimeError('weights drawn')\n"
            "engine.StageServer.weights = drawn\n"
            f"sys.argv = ['bench/run.py', '--workload', {bench_smoke.CELL!r}, '--seed', "
            "'2147483999', '--seconds', '1', '--trace', '0']\n"
            "runpy.run_path('bench/run.py', run_name='__main__')\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO / "src")}
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "correct" not in proc.stdout
    assert "bench/reference/no_such_family.py" in proc.stderr
    assert "weights drawn" not in proc.stderr


# (FLOPs, bytes) of one forward at batches 1-8, as the benchmark counted them
# before the families had files of their own
COUNTS = {
    (0, 32): [(53147320320, 308876032), (106294640640, 311229440),
              (159441960960, 313582848), (212589281280, 315936256),
              (265736601600, 318289664), (318883921920, 320643072),
              (372031242240, 322996480), (425178562560, 325349888)],
    (0, 128): [(85413789696, 309171712), (170827579392, 311673344),
               (256241369088, 314174976), (341655158784, 316676608),
               (427068948480, 319178240), (512482738176, 321679872),
               (597896527872, 324181504), (683310317568, 326683136)],
    (1, 32): [(194072150016, 6060755200), (388144300032, 6060952064),
              (582216450048, 6061148928), (776288600064, 6061345792),
              (970360750080, 6061542656), (1164432900096, 6061739520),
              (1358505050112, 6061936384), (1552577200128, 6062133248)],
    (1, 128): [(778553524224, 6061345792), (1557107048448, 6062133248),
               (2335660572672, 6062920704), (3114214096896, 6063708160),
               (3892767621120, 6064495616), (4671321145344, 6065283072),
               (5449874669568, 6066070528), (6228428193792, 6066857984)],
}


@pytest.mark.parametrize("stage,seq", sorted(COUNTS))
def test_family_cost_counts_keep_their_numbers(stage, seq):
    st = STAGES[stage]
    counts = [reference.family(st).forward_cost(st, b, seq) for b in range(1, 9)]
    assert counts == COUNTS[stage, seq]
    assert [flops.forward_cost(st, b, seq) for b in range(1, 9)] == COUNTS[stage, seq]


@pytest.mark.parametrize("fixture,program", [("tpu_window", "jit_fwd"), ("tpu_train", None)])
def test_op_seconds_holds_every_operation_once(fixture, program):
    s = trace.reduce(DATA / f"{fixture}.xplane.pb", program=program)
    ranked = sorted(s.op_seconds.items(), key=lambda kv: -kv[1])[:10]
    assert s.top_ops == [[k, v] for k, v in ranked]
    # a while loop's body follows the loop's own op: only the body counts
    assert not any(k.endswith("/while") for k in s.op_seconds)
    assert sum(s.op_seconds.values()) <= s.busy_s + 1e-9


def test_context_holds_the_stage_counters_around_the_window(tmp_path):
    cell, result = _run(bench_smoke.make(tmp_path))
    stats = result.context["stage_stats"]
    calls = result.context["calls"]
    assert len(stats["before"]) == len(stats["after"]) == len(cell.config["stages"])
    for i, (before, after) in enumerate(zip(stats["before"], stats["after"], strict=True)):
        ran = sum(c.stage == i for c in calls)
        assert ran > 0
        batches = [sum(v["batches"] for v in s["executed"].values()) for s in (before, after)]
        assert batches[1] - batches[0] == ran
        assert after["compiled_shapes"] == before["compiled_shapes"]
    # the Whisper stage's stub frames: drawn in set-up, reused in the window
    before, after = stats["before"][0]["stub_inputs"], stats["after"][0]["stub_inputs"]
    assert after["drawn"] == before["drawn"]
    assert after["reused"] - before["reused"] == sum(c.stage == 0 for c in calls)
