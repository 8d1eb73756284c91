"""The yardstick's arithmetic on the CPU: operation and byte counts from
shapes against a hand computation, and the trace reduction against small
traces recorded by ``bench/tools/record_trace.py``, on a TPU v5e and on
the CPU."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import bench_smoke

REPO = bench_smoke.REPO
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import flops, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
STAGES = json.loads((REPO / "bench" / "configs" / "speech2code.json").read_text())["stages"]
V5E = flops.peaks("TPU v5 lite")


def test_starcoder2_3b_forward_at_batch_8_by_256_matches_a_hand_count():
    f, b = flops.forward_cost(STAGES[1], 8, 256)
    # matmuls against weights, per layer: q and o 3072x3072, k and v
    # 3072x256, the MLP 2 x 3072x12288 = 95,944,704 multiply-adds per
    # token; 30 layers, 2048 tokens, 2 FLOPs each
    weights = 2 * 2048 * 30 * 95_944_704
    head = 2 * 2048 * 3072 * 49152
    # causal QK^T and PV: 8 x 24 heads x 128 wide x 256*257/2 pairs
    attention = 2 * 2 * 8 * 24 * 128 * 32_896 * 30
    assert f == weights + head + attention == 12_505_174_769_664
    # bf16 weights (per layer 95,944,704 + 18,944 biases + 12,288 norm
    # entries; the head 3072x49152 and the final norm), the 2048 embedding
    # rows gathered, int32 tokens in and out
    params = 30 * (95_944_704 + 18_944 + 12_288) + 3072 * 49152 + 2 * 3072
    assert b == 2 * params + 2 * 2048 * 3072 + 8 * 256 * 4 * 2 == 6_073_157_632
    t, bound = flops.roofline_s(STAGES[1], 8, 256, V5E)
    assert bound == "compute" and t == pytest.approx(f / 197e12)


def test_short_prompts_are_bound_by_weight_streaming():
    t, bound = flops.roofline_s(STAGES[1], 1, 32, V5E)
    assert bound == "memory" and t == pytest.approx(flops.forward_cost(STAGES[1], 1, 32)[1] / 819e9)
    f, b = flops.forward_cost(STAGES[0], 8, 256)
    # whisper-small's decoder: 1.0 TFLOP at 8 x 256, most of it the
    # projection of 1500 stub frames and the cross-attention over them
    assert 0.9e12 < f < 1.2e12 and 0.3e9 < b < 0.5e9


def _brute_busy(path, w0, w1, line="XLA Ops") -> float:
    from jax.profiler import ProfileData
    plane = next(p for p in ProfileData.from_file(str(path)).planes
                 if p.name.startswith("/device:"))
    ns = np.zeros(int(w1 - w0) + 2, dtype=bool)
    for ln in plane.lines:
        if ln.name == line:
            for ev in ln.events:
                s = max(int(round(ev.start_ns - w0)), 0)
                e = min(int(round(ev.start_ns + ev.duration_ns - w0)), ns.size)
                ns[s:e] = True
    return ns.sum() * 1e-9


def test_trace_reduction_of_a_tpu_window():
    path = DATA / "tpu_window.xplane.pb"
    # the device's clock runs about a millisecond early: unaligned, the
    # first execution falls before the window
    assert [p.name for p in trace.reduce(path).programs] == ["jit_fwd"] * 2
    s = trace.reduce(path, program="jit_fwd")
    assert 1e-3 < s.skew_s < 2e-3
    assert s.devices == 1
    assert [p.name for p in s.programs] == ["jit_fwd"] * 3
    assert 0 < s.busy_s < s.window_s < 0.1
    w0 = trace._host_spans(list(__import__("jax").profiler.ProfileData.from_file(
        str(path)).planes), trace.WINDOW_SPAN, trace.DISPATCH_PREFIX)[0][0]
    assert s.busy_s == pytest.approx(
        _brute_busy(path, w0 - s.skew_s * 1e9, w0 - s.skew_s * 1e9 + s.window_s * 1e9),
        abs=2e-8)
    assert sum(p.seconds for p in s.programs) <= s.window_s
    named = trace.reduce(path, program="jit_fwd",
                         name_programs=lambda ps: [f"call{k}" for k in range(len(ps))])
    assert [p.label for p in named.programs] == ["call0", "call1", "call2"]
    gaps = dict(named.idle_gaps)
    assert "call0 -> call1" in gaps and "call1 -> call2" in gaps
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert all(name.split("/")[0].startswith("call") for name, _ in named.top_ops)


def test_trace_without_a_device_plane_reduces_to_nothing():
    assert trace.reduce(DATA / "cpu_window.xplane.pb") is None


def test_helpers():
    assert trace.program_name("jit_fwd(6836809064968452356)") == "jit_fwd"
    assert trace.op_name("%fusion.12 = bf16[8]{0} fusion(x)") == "fusion"
    assert trace.merge([(3, 5), (0, 1), (4, 7)]) == [[0, 1], [3, 7]]


def test_top_operations_count_no_time_twice():
    s = trace.reduce(DATA / "tpu_window.xplane.pb", program="jit_fwd")
    assert sum(v for _, v in s.top_ops) <= s.busy_s + 1e-9
