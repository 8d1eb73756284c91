"""The host's cost of one program span (``jax.profiler.TraceAnnotation``
entered and left), with the profiler off and on: a bare span, one with the
attributes of ``stage.call``, and the request-id string alone.

    python3 bench/tools/span_cost.py

Prints one JSON line of microseconds per span, best of five repeats.
"""
import json
import shutil
import tempfile
import timeit

import jax
from jax.profiler import TraceAnnotation

RIDS = list(range(100_000, 100_008))       # a full batch of eight


def bare():
    with TraceAnnotation("stage.wait"):
        pass


def call():
    rids = ";".join(str(r) for r in RIDS) if TraceAnnotation.is_enabled() else ""
    with TraceAnnotation("stage.call", stage=1, z=0, batch=8, rids=rids):
        pass


def join():
    ";".join(str(r) for r in RIDS)


def best_us(f, n=100_000) -> float:
    return min(timeit.repeat(f, number=n, repeat=5)) / n * 1e6


def main() -> None:
    out = {"device": jax.devices()[0].device_kind}
    for state in ("off", "on"):
        tmp = None
        if state == "on":
            tmp = tempfile.mkdtemp()
            jax.profiler.start_trace(tmp)
        for f in (bare, call, join):
            out[f"{f.__name__}_{state}_us"] = best_us(f)
        if tmp:
            jax.profiler.stop_trace()
            shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
