"""The span reduction (``bench/spans.py``) against a small TPU trace
recorded by ``bench/tools/record_span_trace.py``: three forwards under the
program's span names, with known sleeps inside ``stage.prepare``, inside
``stage.wait`` after the output is ready, and between calls inside
``runtime.advance``."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

import bench_smoke

REPO = bench_smoke.REPO
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness, spans, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "tpu_spans.xplane.pb"
SLACK = 2e-4        # each sleep lands in its bucket to within its length less this


def _tool():
    path = REPO / "bench" / "tools" / "record_span_trace.py"
    spec = importlib.util.spec_from_file_location("record_span_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TOOL = _tool()


def _reduced(path):
    summary = trace.reduce(path, program="jit_fwd")
    return summary, spans.reduce(path, summary, program="jit_fwd")


def test_the_three_shares_sum_to_the_device_idle_share():
    summary, split = _reduced(FIXTURE)
    shares = [100.0 * t / split.window_s
              for t in (split.runtime_s, split.prepare_s, split.call_s)]
    assert all(0 <= v <= 100 for v in shares)
    result = harness.Result(metrics={}, checks={}, attempted=0, failed=0, device={},
                            context={"trace": summary})
    assert sum(shares) == pytest.approx(
        harness.reader("device_idle_pct.serve")(result), abs=0.01)
    assert split.idle_s == pytest.approx(summary.window_s - summary.busy_s, abs=1e-9)
    by_span = sum(v for _, v in split.idle_by_span)
    assert by_span == pytest.approx(split.idle_s, abs=1e-9)


def _host_time(path, name) -> float:
    """Seconds in which ``name`` is the innermost program span."""
    from jax.profiler import ProfileData
    planes = ProfileData.from_file(str(path)).planes
    found = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for p in planes if p.name.startswith("/host:")
             for ln in p.lines for ev in ln.events if ev.name in spans.PROGRAM_SPANS]
    return sum(b - a for a, b, names in spans.segments(found, 0, 1e12)
               if names and names[-1] == name) * 1e-9


@pytest.mark.parametrize("span, bucket, sleep", [
    ("stage.prepare", "prepare_s", TOOL.PREPARE_S),
    ("stage.wait", "call_s", TOOL.WAIT_S),
    ("runtime.advance", "runtime_s", TOOL.BETWEEN_S),
])
def test_each_sleep_lands_in_its_own_bucket(span, bucket, sleep):
    _, split = _reduced(FIXTURE)
    idle = dict(split.idle_by_span)[span]
    assert TOOL.CALLS * (sleep - SLACK) <= idle <= _host_time(FIXTURE, span)
    assert getattr(split, bucket) >= TOOL.CALLS * (sleep - SLACK)


def test_every_forward_lies_inside_its_dispatch_to_wait_spans():
    summary, split = _reduced(FIXTURE)
    assert split.forwards == TOOL.CALLS
    assert split.outside == 0 and split.slack_s > 0
    # the harness's span opens before stage.prepare: its shift is smaller
    # and starts a forward before the host dispatched it
    assert split.skew_s > summary.skew_s
    from jax.profiler import ProfileData
    dispatch = sorted(ev.start_ns for p in ProfileData.from_file(str(FIXTURE)).planes
                      for ln in p.lines for ev in ln.events
                      if ev.name == "stage.dispatch")
    assert any(p.start < d for p, d in zip(summary.programs, dispatch, strict=True))


@pytest.mark.parametrize("fixture", ["tpu_window.xplane.pb", "cpu_window.xplane.pb"])
def test_a_trace_without_program_spans_reduces_to_nothing(fixture):
    assert _reduced(DATA / fixture)[1] is None


def test_segments_name_the_innermost_span():
    cut = spans.segments([(0, 10, "a"), (2, 5, "b"), (3, 4, "c"), (12, 20, "d")],
                         1, 15)
    assert cut == [(1, 2, ("a",)), (2, 3, ("a", "b")), (3, 4, ("a", "b", "c")),
                   (4, 5, ("a", "b")), (5, 10, ("a",)), (10, 12, ()),
                   (12, 15, ("d",))]
