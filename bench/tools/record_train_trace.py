"""Records the small TPU trace that ``bench/tests/test_bench_train.py``
checks the training cell's trace reading against: a window span around
two episodes, each under the driver's episode span, each one call of a
jitted loop named as the twin's rollout program (``jit_vec_rollout``) and
then three calls of a jitted step named as the PPO update
(``jit_ppo_minibatch_update``), each read back by the host; the host
sleeps a known time before each rollout and after each update's read.

    python3 bench/tools/record_train_trace.py <out.xplane.pb>

Run it on the chip; the trace is a few tens of KB.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

EPISODES, UPDATES = 2, 3
BEFORE_ROLLOUT_S, AFTER_UPDATE_S = 0.003, 0.001


def vec_rollout(x):
    return jax.lax.fori_loop(0, 64, lambda i, h: jnp.tanh(h @ x), x)


def ppo_minibatch_update(x):
    return (x @ x).sum()


def main(out: str) -> None:
    rollout, update = jax.jit(vec_rollout), jax.jit(ppo_minibatch_update)
    x = jnp.ones((512, 512), jnp.float32) / 512
    rollout(x).block_until_ready()
    float(update(x))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        for _ in range(EPISODES):
            with TraceAnnotation("bench.episode"):
                time.sleep(BEFORE_ROLLOUT_S)
                h = rollout(x)
                for _ in range(UPDATES):
                    float(update(h))
                    time.sleep(AFTER_UPDATE_S)
    jax.profiler.stop_trace()
    shutil.copy(next(Path(tmp).rglob("*.xplane.pb")), out)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
