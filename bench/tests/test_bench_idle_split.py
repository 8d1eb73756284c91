"""The served cells' three idle metrics (``idle_runtime_pct.serve``,
``idle_prepare_pct.serve``, ``idle_call_pct.serve``) on the TPU trace
that ``bench/tools/record_span_trace.py`` recorded: each reads its bucket
of ``bench/spans.py``'s split, and the three add up to
``device_idle_pct.serve``."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

import bench_smoke

REPO = bench_smoke.REPO
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness, spans, trace  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / "tpu_spans.xplane.pb"
SPLIT = ("idle_runtime_pct.serve", "idle_prepare_pct.serve", "idle_call_pct.serve")


def _result(**context) -> harness.Result:
    return harness.Result(metrics={}, checks={}, attempted=0, failed=0, device={},
                          context=context)


def test_idle_split_metrics_add_up_to_the_device_idle_share():
    summary = trace.reduce(FIXTURE, program="jit_fwd")
    host = spans.reduce(FIXTURE, summary, program="jit_fwd")
    result = _result(trace=summary, spans=host)
    parts = [harness.reader(m)(result) for m in SPLIT]
    assert parts == pytest.approx([100 * x / host.window_s for x in
                                   (host.runtime_s, host.prepare_s, host.call_s)])
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(harness.reader("device_idle_pct.serve")(result),
                                       abs=0.5)


@pytest.mark.parametrize("metric", SPLIT)
def test_idle_split_metric_is_silent_without_program_spans(metric):
    assert harness.reader(metric)(_result(trace=None, spans=None)) is None
    assert harness.reader(metric)(_result()) is None
