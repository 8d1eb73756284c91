"""Finds a cell's files by name, checks the device, runs the cell's driver
and prints the result.

    BENCHMARK.json            the cells, configurations and metrics
    bench/configs/<config>    a configuration (its ``driver`` names the driver)
    bench/traffic/<mix>.json  a traffic mix
    bench/limits/<cell>.json  the limits the cell's outputs are held to
    bench/drivers/<driver>.py ``run(cell, args, clock) -> Result``
    bench/metrics/<metric>.py ``read(result) -> float | None``

A later cell, mix or metric is added by adding files and entries; nothing
here names one.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path


@dataclass
class Result:
    """What a driver hands back: the end-to-end numbers, the checks (name
    -> (value, limit)), and whatever its per-layer readers read."""
    metrics: dict[str, float]
    checks: dict[str, tuple[float, float]]
    attempted: int
    failed: int
    device: dict
    breakdown: dict | None = None
    context: dict = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(math.isfinite(v) and v <= lim for v, lim in self.checks.values())


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix, limits and metrics."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "limits" / f"{name}.json").read_text())
    return Cell(name=name, config=config, traffic=traffic, limits=limits,
                chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                root=root)


def driver(cell: Cell):
    """The driver module the cell's configuration names."""
    path = cell.root / "bench" / "drivers" / f"{cell.config['driver']}.py"
    return _load(path, f"bench_driver_{cell.config['driver']}")


def reader(metric: str, root: Path = ROOT):
    """The ``read`` function of a per-layer metric's own file."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    return _load(path, "bench_metric_" + metric.replace(".", "_")).read


def _load(path: Path, module_name: str):
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = mod
    spec.loader.exec_module(mod)
    return mod


def check_device(chips: int) -> dict:
    """The accelerator JAX found; exits non-zero on any other platform or
    on fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found platform "
                         f"{d.platform!r} ({d.device_kind}), {len(devices)} device(s)")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips; JAX found "
                         f"{len(devices)} {d.device_kind}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def note(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def result_line(cell: Cell, result: Result, trace: bool) -> dict:
    """The last line of standard output: end-to-end metrics with
    ``--trace 0``, per-layer metrics (each by its own reader) with
    ``--trace 1``; the compared numbers come last."""
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = reader(m["name"], cell.root)(result)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": result.metrics[m["name"]], "unit": m["unit"]}
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": result.device}
    if trace and result.breakdown is not None:
        line["breakdown"] = result.breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in result.checks.items()}
    return line


@dataclass(frozen=True)
class Args:
    workload: str
    seed: int
    seconds: float
    trace: bool
    control: bool = False       # also read the lower-precision control


def main(argv: list[str] | None = None, *, t0: float | None = None) -> int:
    import argparse
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cell = resolve(a.workload)
    drv = driver(cell)
    device = check_device(cell.chips)
    args = Args(a.workload, a.seed, a.seconds, bool(a.trace))
    result = drv.run(cell, args, t0=t0)
    result.device = {**device, **result.device}
    line = result_line(cell, result, args.trace)
    for k, (v, lim) in result.checks.items():
        note(f"check {k} = {v!r} (limit {lim!r}) {'ok' if v <= lim else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0

