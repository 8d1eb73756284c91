"""Records the small TPU trace that ``bench/tests`` checks the trace
reduction against: a window span around three calls of a jitted forward
(``jit_fwd``), each under a stage span, with idle time between them.

    python3 bench/tools/record_trace.py <out.xplane.pb>

Run it on the chip; the trace is a few tens of KB.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    def fwd(x):
        return jnp.tanh(x @ x).sum()
    fwd = jax.jit(fwd)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    fwd(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.stage0.forward"):
                fwd(x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(next(Path(tmp).rglob("*.xplane.pb")), out)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
