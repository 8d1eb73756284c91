"""Paper-figure reproductions — one module per paper table/figure, plus the
stage calibration.
``PYTHONPATH=src python -m benchmarks.run [--quick] [--out DIR] [--only fig3]``

Emits ``benchmark,metric,value,reference`` CSV (reference = the paper claim
the value validates against) and writes JSON payloads to experiments/results/
(or ``--out DIR``). The ``--quick`` / ``--out`` flags are shared with every
stand-alone benchmark script via ``benchmarks.common.bench_args``.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import bench_args


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated subset: fig3,fig45,fig6,fig7,calibration",
    )
    args = bench_args(parser=ap)

    from benchmarks import (
        fig3_predictor,
        fig45_workloads,
        fig6_decision_time,
        fig7_convergence,
        stage_calibration,
    )
    suites = {
        "fig3": fig3_predictor.run,
        "fig45": fig45_workloads.run,
        "fig6": fig6_decision_time.run,
        "fig7": fig7_convergence.run,
        "calibration": stage_calibration.run,
    }
    wanted = args.only.split(",") if args.only else list(suites)
    print("benchmark,metric,value,reference")
    failures = []
    for name in wanted:
        t0 = time.time()
        try:
            rows = suites[name](quick=args.quick)
        except Exception as e:  # noqa: BLE001 — keep the harness running
            failures.append((name, e))
            print(f"{name},ERROR,{type(e).__name__}: {e},", file=sys.stderr)
            continue
        for r in rows:
            print(",".join(str(x).replace(",", ";") for x in r))
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures: {[n for n, _ in failures]}")


if __name__ == "__main__":
    main()
