"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), the compared numbers last under
``checks``; the set-up split, the in-window compile count and each
compared number beside its limit go to standard error. Exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for. JAX's persistent compilation cache is kept in ``.jax_cache/``
at the root of the checkout, so only a checkout's first run compiles.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the checkout root and the program; not bench/ itself, whose module names
# would shadow the standard library's
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
(ROOT / ".jax_cache").mkdir(exist_ok=True)      # JAX writes into it, does not make it
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
