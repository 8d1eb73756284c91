"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/tools/calibrate.py --workload speech2code.full \
        --seeds 11 12 13 ... --seconds 3 [--out readings.jsonl]

For each seed it runs the cell once with a short window and reads, on the
same sampled requests, the widest and the mean logit gap of the served
tokens (the program's readings) and of the tokens the int8 control puts
first (the control's readings), both against the float32 reference. It first
checks, for the first seed, that the reference draws the same weights as
the served program. One JSON line per seed goes to standard output (and
to ``--out``). The benchmark's own runs never run the control.
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

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.reference import transformer as ref  # noqa: E402

_RENAME = {"q": "wq", "k": "wk", "v": "wv", "o": "wo", "up": "w1", "down": "w2",
           "head": "lm_head", "cross": "cross_attn"}


def program_path(path: tuple[str, ...], family: str) -> tuple[str, ...]:
    """Where a reference weight sits in the served program's tree."""
    whisper = family == "whisper_decoder"
    out = []
    for key in path:
        if key == "self":
            out.append("self_attn" if whisper else "attn")
        elif key == "ln_self" and not whisper:
            out.append("ln_attn")
        else:
            out.append(_RENAME.get(key, key))
    if out[0] in ("embed", "pos"):
        out.append("e")
    return tuple(out)


@jax.jit
def _max_abs_diff(a, b):
    return jax.numpy.max(jax.numpy.abs(a.astype(np.float32) - b.astype(np.float32)))


def weight_mismatch(stage: dict, program_params, seed: int) -> float:
    """Largest absolute difference between the reference's weights and the
    program's, over every leaf (0.0 where they are the same numbers)."""
    mine = ref.init_weights(stage, seed)
    worst = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(mine)[0]:
        node = program_params
        for key in program_path(tuple(p.key for p in path), stage["family"]):
            node = node[key]
        worst = max(worst, float(_max_abs_diff(node, leaf)))
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    cell = harness.resolve(a.workload)
    device = harness.check_device(cell.chips)
    drv = harness.driver(cell)
    sess, servers, _, _ = drv.build(cell, a.seeds[0])
    for i, (srv, stage) in enumerate(zip(servers, cell.config["stages"], strict=True)):
        worst = weight_mismatch(stage, srv.weights(0), srv.seed)
        harness.note(f"stage {i} weights: reference vs program max |diff| {worst!r}")
        srv.params.clear()      # one stage's two copies on the device at a time
    drv.free(servers)
    del sess, servers
    out = open(a.out, "a") if a.out else None
    try:
        for seed in a.seeds:
            t0 = time.perf_counter()
            res = drv.run(cell, harness.Args(a.workload, seed, a.seconds, False, True), t0=t0)
            row = {"workload": a.workload, "seed": seed, "device": device["kind"],
                   "program_gap": res.context.get("program_gap"),
                   "program_mean": res.context.get("program_gap_mean"),
                   "control_gap": res.context.get("control_gap"),
                   "control_mean": res.context.get("control_gap_mean"),
                   "unaccounted": res.checks["unaccounted_requests"][0],
                   "served_req_per_s": res.metrics["served_req_per_s"],
                   "service_p95_ms": res.metrics["service_p95_ms"],
                   "attempted": res.attempted, "calls": len(res.context["calls"]),
                   "memory_peak_bytes": res.device["memory_peak_bytes"],
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
