"""The program's profiler spans: a smoke-width serve2 session with live
stage models, served under ``jax.profiler.trace`` on the CPU, read back
from the trace's host plane."""

import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import api
from repro.cluster.env import RuntimeEnv
from repro.core.mdp import Config

SPANS = (
    "session.serve",
    "controller.decide",
    "runtime.advance",
    "stage.call",
    "stage.prepare",
    "stage.dispatch",
    "stage.compile",
    "stage.wait",
)
# stage 1 switches variant in the second interval, so a new z compiles too
CONFIGS = (
    Config(z=(0, 0), f=(1, 1), b=(3, 3)),
    Config(z=(0, 1), f=(1, 1), b=(3, 3)),
)


class Alternating:
    """Returns the configurations in turn, one per interval."""

    def __init__(self):
        self.k = 0

    def decide(self, obs):
        cfg = CONFIGS[min(self.k, len(CONFIGS) - 1)]
        self.k += 1
        return cfg


class Logged:
    """A stage executor that keeps each call's stage, variant, size and
    output (a copy, so each request's output row points back at it)."""

    def __init__(self, server, stage, log):
        self.server, self.stage, self.log = server, stage, log

    def __call__(self, z, tokens):
        out = np.array(self.server(z, tokens))
        self.log.append((self.stage, int(z), tokens.shape[0], out))
        return out

    def stats(self):
        return self.server.stats()


class Span:
    def __init__(self, ev):
        self.start = ev.start_ns
        self.end = ev.start_ns + ev.duration_ns
        self.stats = dict(ev.stats)

    def rids(self):
        # the profiler reads a lone id back as a number
        return [int(r) for r in str(self.stats["rids"]).split(";")]

    def inside(self, other):
        return other.start <= self.start and self.end <= other.end


@pytest.fixture(scope="module")
def served():
    spec = api.ExperimentSpec(
        pipeline=api.get_pipeline("serve2"),
        scenario=api.ScenarioSpec(kind="poisson", rate=2.0, seed=3, horizon=20),
        controller=api.get_controller("greedy"),
        backend="runtime",
        real=True,
        widths="smoke",
    )
    sess = api.Session.from_spec(spec)
    log = []
    servers = api.build_executors(spec)
    env = RuntimeEnv(
        sess.pipe,
        spec.scenario.build_arrivals(),
        horizon=spec.scenario.horizon,
        executors=[Logged(s, i, log) for i, s in enumerate(servers)],
        seq_len=spec.seq_len,
    )
    sess.build_env = lambda: env
    sess.controller = Alternating()
    tracedir = tempfile.mkdtemp(prefix="spans-")
    with jax.profiler.trace(tracedir):
        report = sess.serve()
    path = next(Path(tracedir).rglob("*.xplane.pb"))
    names, spans = set(), {n: [] for n in SPANS}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    names.add(ev.name)
                    if ev.name in spans:
                        spans[ev.name].append(Span(ev))
    for v in spans.values():
        v.sort(key=lambda e: e.start)
    return report, env, log, spans, names


def test_every_span_is_recorded_and_none_is_a_harness_span(served):
    _, _, _, spans, names = served
    assert all(spans[n] for n in SPANS)
    assert not any(n.startswith("bench.") for n in names)


def test_one_stage_call_per_executor_call_with_its_batch(served):
    _, env, log, spans, _ = served
    calls = spans["stage.call"]
    assert len(calls) == len(log) > 0
    for span, (stage, z, batch, _) in zip(calls, log, strict=True):
        assert span.stats["stage"] == stage
        assert span.stats["z"] == z
        assert span.stats["batch"] == batch == len(span.rids())
    sizes = [(b.stage, b.size) for b in env.runtime.telemetry.batches]
    assert sorted((s, b) for s, _, b, _ in log) == sorted(sizes)


def test_request_ids_name_the_rows_of_each_call(served):
    _, env, log, spans, _ = served
    rids = [s.rids() for s in spans["stage.call"]]
    by_out = {id(out): k for k, (_, _, _, out) in enumerate(log)}
    completed = env.runtime.completed
    assert len(completed) == env.submitted > 0
    for req in completed:
        for stage, view in enumerate(req.stage_outputs):
            k = by_out[id(view.base)]
            out = log[k][3]
            row = (view.__array_interface__["data"][0]
                   - out.__array_interface__["data"][0]) // out.strides[0]
            assert log[k][0] == stage and rids[k][row] == req.rid
            # in exactly one call of each stage
            holding = [j for j, ids in enumerate(rids)
                       if req.rid in ids and log[j][0] == stage]
            assert holding == [k]


def test_prepare_dispatch_and_wait_nest_in_order_inside_each_call(served):
    _, _, _, spans, _ = served
    for call in spans["stage.call"]:
        inner = {n: [s for s in spans[n] if s.inside(call)]
                 for n in ("stage.prepare", "stage.dispatch", "stage.wait")}
        assert all(len(v) == 1 for v in inner.values())
        prepare, dispatch, wait = (inner[n][0] for n in
                                   ("stage.prepare", "stage.dispatch", "stage.wait"))
        assert prepare.end <= dispatch.start and dispatch.end <= wait.start
        assert prepare.stats["batch"] == call.stats["batch"]


def test_a_compile_only_on_a_new_shape_inside_its_dispatch(served):
    _, _, log, spans, _ = served
    compiles = spans["stage.compile"]
    calls = spans["stage.call"]
    seen = set()
    for call, (stage, z, batch, _) in zip(calls, log, strict=True):
        mine = [c for c in compiles if c.inside(call)]
        new = (stage, z, batch) not in seen
        seen.add((stage, z, batch))
        assert len(mine) == int(new)
        for c in mine:
            assert (c.stats["z"], c.stats["batch"]) == (z, batch)
            assert any(c.inside(d) for d in spans["stage.dispatch"])
    assert len(compiles) == len(seen)
    assert {z for _, z, _ in seen} == {0, 1}


def test_prepare_reuses_stub_inputs_on_a_repeated_shape(served):
    _, env, log, spans, _ = served
    prepares = spans["stage.prepare"]
    seen = set()
    for prep, (stage, z, batch, _) in zip(prepares, log, strict=True):
        family = env.executors[stage].server.variants[z].family
        repeat = (stage, z, batch) in seen
        seen.add((stage, z, batch))
        assert prep.stats["reused"] == int(repeat and family in ("audio", "vlm"))
    reused = sum(e.stats()["stub_inputs"]["reused"] for e in env.executors)
    assert reused == sum(p.stats["reused"] for p in prepares) > 0


def test_one_decision_per_interval_inside_the_serve_span(served):
    report, _, _, spans, _ = served
    (serve,) = spans["session.serve"]
    decide = spans["controller.decide"]
    assert len(decide) == len(report["rewards"]) == len(report["decide_wall_s"])
    assert [d.stats["interval"] for d in decide] == list(range(len(decide)))
    assert all(d.inside(serve) for d in decide)


def test_each_advance_names_its_virtual_end(served):
    report, env, _, spans, _ = served
    advance = spans["runtime.advance"]
    # one per interval, then one for the drain, which runs the loop dry
    assert [a.stats["t_end"] for a in advance[:-1]] == [
        10.0 * (k + 1) for k in range(len(report["rewards"]))
    ]
    assert advance[-1].stats["t_end"] == float("inf")
    calls = spans["stage.call"]
    assert all(any(c.inside(a) for a in advance) for c in calls)
