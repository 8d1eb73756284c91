"""Readings that a training cell's limits are set from, on the chip, in one
process.

    python3 bench/tools/calibrate_train.py --workload opd-serve3h.train \
        --seeds 11 12 13 ... --seconds 2 [--out readings.jsonl]

For each seed it runs the cell once with a short window and reads every
number the cell compares, three ways: the program's (as a benchmark run
reads them), each control's (the reference computed in bfloat16, and
with int8 matmuls, in the program's place, against the float32
reference), and each fault's (a step that returns its state unchanged,
half of each minibatch left out, an answer of the twin altered where it
is produced; see the driver's ``fault_readings``). The run's own verdict
is the bfloat16 control's, and reads false where the control fails a
limit (``control_correct``); ``correct`` is the program's. One JSON line
per seed goes to standard output (and to ``--out``). The benchmark's own
runs never run the controls or the faults.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
(ROOT / ".jax_cache").mkdir(exist_ok=True)      # JAX writes into it, does not make it
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    cell = harness.resolve(a.workload)
    device = harness.check_device(cell.chips)
    drv = harness.driver(cell)
    out = open(a.out, "a") if a.out else None
    try:
        for seed in a.seeds:
            t0 = time.perf_counter()
            res = drv.run(cell, harness.Args(a.workload, seed, a.seconds, False, True), t0=t0)
            program = res.context["readings"]
            row = {"workload": a.workload, "seed": seed, "device": device["kind"],
                   "program": program, "controls": res.context["controls"],
                   "faults": res.context["faults"],
                   "correct": all(program[k] <= lim for k, lim in cell.limits.items()),
                   "control_correct": res.correct,
                   "episodes": res.attempted, "metrics": res.metrics,
                   "memory_peak_bytes": res.device["memory_peak_bytes"],
                   "compiles_in_window": res.context["compiles_in_window"],
                   "seconds": time.perf_counter() - t0}
            print(json.dumps(row), flush=True)
            if out:
                out.write(json.dumps(row) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
