"""Records the small TPU trace that ``bench/tests/test_bench_spans.py``
checks the span reduction (``bench/spans.py``) against: a window span
around three calls of a jitted forward (``jit_fwd``), each under the
program's span names, with known sleeps inside ``stage.prepare``, inside
``stage.wait`` after the output is ready, and between calls inside
``runtime.advance``.

    python3 bench/tools/record_span_trace.py <out.xplane.pb>

Run it on the chip; the trace is a few tens of KB. As in the served
cells, the harness's stage span (``bench.stage0.forward``) wraps the
stage server's prepare, dispatch and wait, inside ``stage.call``.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

CALLS = 3
PREPARE_S, WAIT_S, BETWEEN_S = 0.003, 0.002, 0.004


def main(out: str) -> None:
    def fwd(x):
        return jnp.tanh(x @ x).sum()
    fwd = jax.jit(fwd)
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    fwd(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"), TraceAnnotation("session.serve"):
        for k in range(CALLS):
            with TraceAnnotation("runtime.advance", t_end=float(k)):
                time.sleep(BETWEEN_S)
                with TraceAnnotation("stage.call", stage=0, z=0, batch=1,
                                     rids=str(k)), \
                        TraceAnnotation("bench.stage0.forward"):
                    with TraceAnnotation("stage.prepare", batch=1):
                        time.sleep(PREPARE_S)
                    with TraceAnnotation("stage.dispatch"):
                        y = fwd(x)
                    with TraceAnnotation("stage.wait"):
                        y.block_until_ready()
                        time.sleep(WAIT_S)
    jax.profiler.stop_trace()
    shutil.copy(next(Path(tmp).rglob("*.xplane.pb")), out)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
