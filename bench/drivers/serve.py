"""Driver for a served pipeline: live stage models behind
``api.Session.serve``, replayed as fast as the chip allows.

Set-up (``setup_s``, from process start): each stage's model family is
found first, as the file ``bench/reference/<family>.py`` (the run stops,
naming the file, where it is missing); then the session and its stage
servers are built through the program's public API, each stage's weights
drawn on the device from the seed, and every batch size that the mix
will send a stage (the runtime replayed in virtual time without models)
compiled, or read from the compile cache, and run once. A fixed
controller, registered through ``api.register_controller``, returns the
configuration's ``serve_config`` every interval, so the data path is a
function of the traffic and the seed alone.

The window: ``Session.serve`` runs over an env the harness built from the
mix (``bench/arrivals.py``), with each stage executor wrapped by a
``Recorder`` that times the live forward (dispatch to the host read of
its output) and keeps what went in and out. The runtime is virtual-time,
so it replays its schedule as fast as the forwards return; the window
ends at ``--seconds`` by the harness's clock, at the next stage call.
A request counts once its last stage's forward returned in the window.

- ``served_req_per_s``: requests counted over the window's wall time.
- ``service_p95_ms``: per request, the summed wall time of the forwards
  that carried it; the 95th percentile (linear) over all counted.

With ``--trace 1`` the profiler's trace of the window is reduced twice
before it is deleted: by ``bench/trace.py`` (device busy time, programs,
top operations, idle gaps) and by ``bench/spans.py`` (the device's idle
time put down to the program's host spans, ``idle_by_span`` in the
breakdown). The untraced run reads neither.

After the window, with the program's device state freed, the outputs are
checked: every counted request passed every stage once, on the prompt
the mix gave it, each stage fed the previous stage's output; and for a
sample of requests drawn from the seed, every served token's logit is
compared with the float32 reference's best at its position (the stage
family's ``gap_program``): the widest gap or the mean gap of each stage,
as the cell's limits file names them, is held to its limit.

``context["stage_stats"]`` holds each stage server's ``stats()`` taken
just before the window opens and just after it closes, so that a
per-layer metric can read a program counter as the window's difference.
"""
from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import jax
import numpy as np
from repro import api
from repro.cluster.env import RuntimeEnv
from repro.core.mdp import Config

from bench import flops, reference, spans, trace
from bench.arrivals import MixArrivals, prompts
from bench.harness import Cell, Result, note

SAMPLE_TOKENS = 4096        # served tokens per stage that the reference reads
FORWARD = "jit_fwd"         # the served forward program, one per stage call


class WindowClosed(Exception):
    """Raised at the first stage call after the window's end."""


@dataclass
class Call:
    stage: int
    batch: int
    start: float
    end: float
    tokens: np.ndarray      # [B, S] as the stage received them
    out: np.ndarray         # [B, S] the tokens it served


class Window:
    def __init__(self):
        self.calls: list[Call] = []
        self.close_at = math.inf

    def open(self, seconds: float) -> float:
        t = time.perf_counter()
        self.close_at = t + seconds
        return t


class Recorder:
    """Stands in for a stage executor of the runtime."""

    def __init__(self, server, stage: int, window: Window):
        self.server = server
        self.stage = stage
        self.window = window
        self.span = f"bench.stage{stage}.forward"

    def __call__(self, z: int, tokens: np.ndarray) -> np.ndarray:
        if time.perf_counter() >= self.window.close_at:
            raise WindowClosed
        with jax.profiler.TraceAnnotation(self.span):
            t0 = time.perf_counter()
            out = np.array(self.server(z, tokens))
            t1 = time.perf_counter()
        self.window.calls.append(Call(self.stage, tokens.shape[0], t0, t1, tokens, out))
        return out


class FixedController:
    """Returns one configuration every adaptation interval."""

    def __init__(self, cfg):
        self.cfg = cfg

    def decide(self, obs):
        return self.cfg


# ------------------------------------------------------------------ helpers --

def _stage_seed(seed: int, stage: int) -> int:
    """The weight seed of a stage: 31 bits, from the run's seed."""
    return (seed % 2_147_483_000) + stage


def _program_view(arch, stage: dict) -> dict:
    """The stage's sizes as the program will run them, under the
    configuration file's names, to refuse a file that says otherwise."""
    return reference.family(stage).program_view(arch, stage)


class CompileCounter:
    """Counts compiles and compile-cache reads, with their times."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)


def _fixed(cfg: dict) -> Config:
    sc = cfg["serve_config"]
    return Config(z=tuple(sc["z"]), f=tuple(sc["f"]), b=tuple(sc["b"]))


def build(cell: Cell, seed: int):
    """The session, its stage servers and the mix, through the program's
    public API, once every stage's family is found."""
    cfg, mix = cell.config, cell.traffic
    families = [reference.family(stage, cell.root) for stage in cfg["stages"]]
    controller = f"bench-fixed-{cfg['name']}"
    fixed = _fixed(cfg)
    api.register_controller(controller, lambda spec, pipe, params: FixedController(fixed))
    arrivals = MixArrivals(mix, seed)
    horizon = int(math.ceil(arrivals.span / 10.0) * 10)
    spec = api.ExperimentSpec(
        pipeline=api.PipelineSpec(name=cfg["name"],
                                  stages=tuple((s["model"],) for s in cfg["stages"]),
                                  quants=tuple(cfg["quants"])),
        scenario=api.ScenarioSpec(kind="poisson", rate=mix["rate"],
                                  seed=seed % 2**31, horizon=horizon),
        controller=api.ControllerSpec(name=controller),
        backend="runtime", real=True, seq_len=mix["seq_len"], widths=cfg["widths"])
    sess = api.Session.from_spec(spec)
    servers = api.build_executors(spec)
    for i, (srv, fam, stage) in enumerate(zip(servers, families, cfg["stages"],
                                              strict=True)):
        have = fam.program_view(srv.variants[0], stage)
        want = {k: stage[k] for k in have}
        if have != want:
            raise SystemExit(f"bench: stage {i} runs {have}, the configuration "
                             f"file states {want}")
        srv.seed = _stage_seed(seed, i)
    return sess, servers, arrivals, horizon


def batch_sizes(make_env, config) -> list[list[int]]:
    """The batch sizes each stage will be sent: the runtime replayed over
    the whole mix in virtual time, without models. Virtual time does not
    depend on what the models return, so the window sends no others."""
    env = make_env(None)
    done = False
    while not done:
        _, _, done, _ = env.step(config)
    env.drain()
    sizes = [set() for _ in env.runtime.stages]
    for b in env.runtime.telemetry.batches:
        sizes[b.stage].add(b.size)
    return [sorted(s) for s in sizes]


def warm_up(servers, sizes: list[list[int]], seq_len: int, split: dict) -> None:
    """Draw each stage's weights and run each batch size it will see once."""
    for i, srv in enumerate(servers):
        t = time.perf_counter()
        jax.block_until_ready(srv.weights(0))
        split[f"weights.stage{i}"] = time.perf_counter() - t
        for b in sizes[i]:
            t = time.perf_counter()
            srv.execute(0, np.ones((b, seq_len), np.int32))
            split[f"shape.stage{i}.B{b}"] = time.perf_counter() - t


# -------------------------------------------------------------- the checks --

def account(calls: list[Call], completed, n_stages: int, mix: dict,
            seed: int) -> tuple[int, dict]:
    """Requests that did not pass every stage once, on their own prompt,
    each stage fed the previous one's output; and where each request's
    rows are: rid -> [(call index, row)] per stage."""
    by_out = {id(c.out): k for k, c in enumerate(calls)}
    used: set[tuple[int, int]] = set()
    rows: dict[int, list[tuple[int, int]]] = {}
    top = max((r.rid for r in completed), default=-1)
    given = prompts(seed, top + 1, mix["prompt_vocab"], mix["seq_len"])
    bad = 0
    for r in completed:
        ok = len(r.stage_outputs) == n_stages
        where = []
        for i, view in enumerate(r.stage_outputs if ok else []):
            k = by_out.get(id(view.base))
            if k is None or calls[k].stage != i:
                ok = False
                break
            c = calls[k]
            row = (view.__array_interface__["data"][0]
                   - c.out.__array_interface__["data"][0]) // c.out.strides[0]
            fed = given[r.rid] if i == 0 else r.stage_outputs[i - 1]
            if (k, row) in used or not np.array_equal(c.tokens[row], fed):
                ok = False
                break
            used.add((k, row))
            where.append((k, int(row)))
        ok = ok and r.result is not None and np.array_equal(r.result, r.stage_outputs[-1])
        bad += not ok
        rows[r.rid] = where
    return bad, rows


def reference_gaps(cfg: dict, calls: list[Call], picks: list[list[tuple[int, int]]],
                   seed: int, control: bool = False):
    """Per stage, [request, position]: how far each sampled request's
    served token logits lie below the float32 reference's best at their
    position. With ``control``, also the same for the tokens the int8
    control puts first."""
    served, low = [], []
    for i, stage in enumerate(cfg["stages"]):
        fam = reference.family(stage)
        where = [p[i] for p in picks]
        tokens = np.stack([calls[k].tokens[r] for k, r in where])
        out_tokens = np.stack([calls[k].out[r] for k, r in where])
        weights = fam.init_weights(stage, _stage_seed(seed, i))
        inputs = zip(*(fam.reference_inputs(stage, calls[k].batch, r) for k, r in where))
        args = [weights, tokens, out_tokens,
                *(np.stack([np.asarray(x) for x in column]) for column in inputs)]
        out = fam.gap_program(stage, control=control)(*args)
        gaps, ctl = out if control else (out, None)
        served.append(np.asarray(gaps))
        if control:
            low.append(np.asarray(ctl))
        del weights, args, out
    return served, (low if control else None)


def sample(rids: list[int], seed: int, seq_len: int) -> list[int]:
    n = min(len(rids), max(1, -(-SAMPLE_TOKENS // seq_len)))
    rng = np.random.default_rng([seed, 0x73616D70])
    return sorted(rng.choice(sorted(rids), size=n, replace=False).tolist())


def free(servers) -> None:
    for srv in servers:
        srv.params.clear()
        srv._compiled.clear()
    gc.collect()


# --------------------------------------------------------------------- run --

def run(cell: Cell, args, *, t0: float) -> Result:
    cfg, mix = cell.config, cell.traffic
    cap = max(cfg["serve_config"]["b"])
    peak = flops.peaks(jax.devices()[0].device_kind) if args.trace else None
    counter = CompileCounter()
    split: dict[str, float] = {"jax_init": time.perf_counter() - t0}
    t = time.perf_counter()
    sess, servers, arrivals, horizon = build(cell, args.seed)

    def make_env(executors):
        return RuntimeEnv(sess.pipe, arrivals, horizon=horizon, executors=executors,
                          max_wait=mix["max_wait"], seq_len=mix["seq_len"],
                          vocab=mix["prompt_vocab"])

    sizes = batch_sizes(make_env, _fixed(cfg))
    split["build"] = time.perf_counter() - t
    note(f"batch sizes the mix sends each stage: {sizes}")
    warm_up(servers, sizes, mix["seq_len"], split)

    window = Window()
    env = make_env([Recorder(s, i, window) for i, s in enumerate(servers)])
    sess.build_env = lambda: env        # serve() drives the env built here

    stats_before = [srv.stats() for srv in servers]
    tracedir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if tracedir:
        jax.profiler.start_trace(tracedir)
    setup_s = time.perf_counter() - t0
    t_open = window.open(args.seconds)
    ran_out = False
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        try:
            sess.serve()
            ran_out = True
        except WindowClosed:
            pass
    t_close = time.perf_counter()
    if tracedir:
        jax.profiler.stop_trace()
    stats_after = [srv.stats() for srv in servers]
    if ran_out:
        raise SystemExit("bench: the mix ran out of requests before the window "
                         "closed; give it more requests")
    wall = t_close - t_open
    calls = window.calls
    completed = list(env.runtime.completed)
    in_window = counter.between(t_open, t_close)
    note("set-up split (s): " + ", ".join(f"{k} {v:.3f}" for k, v in split.items()))
    note(f"setup_s {setup_s:.3f}; compiles or cache reads inside the window: {in_window}")

    memory = jax.devices()[0].memory_stats() or {}
    peak_bytes = int(memory.get("peak_bytes_in_use", 0))
    free(servers)
    del sess, env

    duration = {id(c.out): c.end - c.start for c in calls}
    service = np.array([sum(duration.get(id(v.base), 0.0) for v in r.stage_outputs)
                        for r in completed]) * 1e3
    p95 = float(np.percentile(service, 95)) if service.size else math.inf
    per_second = np.bincount([int(c.end - t_open) for c in calls]).tolist()
    note(f"window {wall:.3f} s: {len(completed)} requests served, "
         f"{len(calls)} stage calls ({per_second} per second), "
         f"{sum(c.end - c.start for c in calls):.3f} s in them; service ms "
         f"p95 {p95:.3f} over {service.size} requests")

    bad, rows = account(calls, completed, len(cfg["stages"]), mix, args.seed)
    picked = sample([r for r, w in rows.items() if len(w) == len(cfg["stages"])],
                    args.seed, mix["seq_len"])
    limits = cell.limits
    checks = {"unaccounted_requests": (float(bad), float(limits["unaccounted_requests"]))}
    failed = bad
    extra: dict = {}
    if picked:
        t = time.perf_counter()
        gaps, control = reference_gaps(cfg, calls, [rows[r] for r in picked],
                                       args.seed, args.control)
        reference_s = time.perf_counter() - t
        for i, g in enumerate(gaps):
            # the limits file names the numbers compared; the others are printed
            for name, value, per_request in (
                    (f"logit_gap.stage{i}", g.max(), g.max(axis=1)),
                    (f"logit_gap_mean.stage{i}", g.mean(), None)):
                if name not in limits:
                    note(f"{name} = {float(value)!r} (not compared)")
                    continue
                lim = float(limits[name])
                checks[name] = (float(value), lim)
                if per_request is not None:
                    failed += int((per_request > lim).sum())
                elif value > lim:
                    failed += len(g)        # the mean is over every sampled request
        note(f"reference read {len(picked)} sampled requests x {mix['seq_len']} "
             f"tokens per stage in {reference_s:.3f} s")
        extra["program_gap"] = [float(g.max()) for g in gaps]
        extra["program_gap_mean"] = [float(g.mean()) for g in gaps]
        if control is not None:
            extra["control_gap"] = [float(c.max()) for c in control]
            extra["control_gap_mean"] = [float(c.mean()) for c in control]
    else:
        checks["sampled_requests"] = (math.inf, 0.0)

    summary = host = None
    if tracedir:
        xplane = next(iter(_xplanes(tracedir)))
        summary = trace.reduce(xplane, program=FORWARD,
                               name_programs=lambda ps: _name_programs(ps, calls, cfg))
        host = spans.reduce(xplane, summary, program=FORWARD)
        shutil.rmtree(tracedir, ignore_errors=True)
    device = {"memory_peak_bytes": peak_bytes}
    breakdown = None
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = {"device_ops": summary.top_ops, "idle_gaps": summary.idle_gaps}
    if host is not None:
        breakdown["idle_by_span"] = host.idle_by_span[:10]
        note(f"program spans: {host.outside} of {host.forwards} forwards outside "
             f"their dispatch-to-wait spans; skew_s {host.skew_s!r}, "
             f"slack_s {host.slack_s!r}")
    return Result(
        metrics={"served_req_per_s": len(completed) / wall,
                 "service_p95_ms": p95,
                 "setup_s": setup_s},
        checks=checks, attempted=len(completed), failed=failed, device=device,
        breakdown=breakdown,
        context={"calls": calls, "window_s": wall, "config": cfg, "mix": mix,
                 "cap": cap, "peak": peak, "trace": summary, "spans": host,
                 "setup_split": split,
                 "stage_stats": {"before": stats_before, "after": stats_after},
                 "compiles_in_window": in_window, **extra})


def _xplanes(tracedir: str):
    return sorted(Path(tracedir).rglob("*.xplane.pb"))


def _name_programs(programs, calls: list[Call], cfg: dict) -> list[str]:
    """The k-th execution of a forward program is the k-th stage call."""
    names, k = [], 0
    for p in programs:
        if p.name == FORWARD and k < len(calls):
            names.append(f"stage{calls[k].stage}.{cfg['stages'][calls[k].stage]['model']}.forward")
            k += 1
        else:
            names.append(p.name)
    return names
