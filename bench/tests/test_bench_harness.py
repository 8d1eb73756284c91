"""CPU tests of the benchmark harness: every cell resolves to its files, a
cell and a metric added as files are found, the serve driver yields a
well-formed result line at smoke widths, and the command refuses a
machine without a TPU."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import bench_smoke

REPO = bench_smoke.REPO
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import flops, harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


def test_benchmark_file_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]] + [w["name"] for w in BENCH["workloads"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = harness.resolve(cell)
    assert harness.driver(c).run
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"} and len(c.end_to_end) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(harness.reader(m["name"]))
    assert "unaccounted_requests" in c.limits
    for i in range(len(c.config["stages"])):
        assert {f"logit_gap.stage{i}", f"logit_gap_mean.stage{i}"} & set(c.limits)


def test_configuration_files_state_what_the_program_runs():
    from repro.configs import ARCHS
    drv = harness.driver(harness.resolve(BENCH["workloads"][0]["name"]))
    for c in BENCH["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        for stage in cfg["stages"]:
            arch = ARCHS[stage["model"]].replace(dtype=stage["torch_dtype"])
            have = drv._program_view(arch, stage)
            assert have == {k: stage[k] for k in have}


def test_peaks_table_refuses_an_unknown_device():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_a_cell_and_a_metric_added_as_files_are_found(tmp_path):
    root = bench_smoke.make(tmp_path)
    (root / "bench" / "metrics" / "calls_per_s.py").write_text(
        "def read(result):\n"
        "    calls = result.context.get('calls')\n"
        "    return len(calls) / result.context['window_s'] if calls else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "calls_per_s", "unit": "1/s", "better": "higher",
                               "source": "program_counter", "layer": "runtime",
                               "moves": "served_req_per_s",
                               "workloads": [bench_smoke.CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve(bench_smoke.CELL, root=root)
    assert cell.config["widths"] == "smoke" and cell.traffic["seq_len"] == 32
    assert "calls_per_s" in [m["name"] for m in cell.per_layer]
    fake = harness.Result(metrics={}, checks={}, attempted=0, failed=0, device={},
                          context={"calls": [1, 2, 3], "window_s": 1.5})
    assert harness.reader("calls_per_s", root)(fake) == 2.0
    empty = harness.Result(metrics={}, checks={}, attempted=0, failed=0, device={})
    assert harness.reader("device_idle_pct.serve", root)(empty) is None


def _run_smoke(root: Path, trace: bool, monkeypatch):
    cell = harness.resolve(bench_smoke.CELL, root=root)
    monkeypatch.setattr(flops, "peaks", lambda kind: {"bf16_flops_per_s": 1e12,
                                                      "hbm_bytes_per_s": 1e11})
    args = harness.Args(bench_smoke.CELL, 2**31 + 977, 1.0, trace)
    result = harness.driver(cell).run(cell, args, t0=time.perf_counter())
    return cell, result, harness.result_line(cell, result, trace)


def test_serve_driver_yields_a_result_line_at_smoke_widths(tmp_path, monkeypatch):
    cell, result, line = _run_smoke(bench_smoke.make(tmp_path), False, monkeypatch)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert result.context["compiles_in_window"] == 0
    assert set(line["checks"]) == {"unaccounted_requests", "logit_gap.stage0",
                                   "logit_gap.stage1", "logit_gap_mean.stage0",
                                   "logit_gap_mean.stage1"}
    json.dumps(line)


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch):
    cell, result, line = _run_smoke(bench_smoke.make(tmp_path), True, monkeypatch)
    assert line["correct"] is True
    # the CPU's trace holds no device plane: device-trace metrics stay silent
    assert set(line["metrics"]) == {"batch_occupancy_pct", "forward_share_pct",
                                    "serve_mfu_pct", "service_mfu_pct"}
    assert 0 < line["metrics"]["batch_occupancy_pct"]["value"] <= 100
    assert 0 < line["metrics"]["forward_share_pct"]["value"] <= 100


def _command(cwd: Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           BENCH["workloads"][0]["name"], "--seed", "2147483999",
                           "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_on_the_cpu_exits_nonzero_and_names_the_platform():
    proc = _command(REPO)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert "correct" not in proc.stdout


def test_command_without_the_program_exits_nonzero(tmp_path):
    bench_smoke.make(tmp_path)
    proc = _command(tmp_path)
    assert proc.returncode != 0 and "correct" not in proc.stdout
    assert "No module named 'repro'" in proc.stderr


def test_every_seed_gets_the_same_gaps_in_another_order():
    from bench.arrivals import MixArrivals, unit_gaps
    mix = {"rate": 4.0, "requests": 500}
    a, b = MixArrivals(mix, 1), MixArrivals(mix, 2**31 + 5)
    gaps = [np.sort(np.diff(np.concatenate([[0.0], m.times]))) for m in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)
    np.testing.assert_allclose(gaps[0], np.sort(unit_gaps(500)) / 4.0, rtol=1e-9)
    assert not np.array_equal(a.times, b.times)
    assert a.rates(3).tolist() == [4.0, 4.0, 4.0]
