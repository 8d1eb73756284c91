"""Shared benchmark plumbing: CLI flags, result paths, OPD policy training
cache, CSV emission. Every benchmark module exposes ``run(quick: bool) ->
list[row]`` where a row is (benchmark, metric, value, reference) —
``reference`` is the paper's claim the value should be compared against (or
"" if none) — and a ``__main__`` that delegates to ``bench_main`` so the
``--quick`` / ``--out DIR`` flags behave identically everywhere.
"""
from __future__ import annotations

import argparse
import json
import os
import pickle

import numpy as np

# the ONE timing loop (min-of-k, warmup + block_until_ready) every benchmark
# shares with the stage executor — canonical home is repro.timing so src-side
# code can use it without importing benchmarks
from repro import compile_cache
from repro.timing import Timing, time_fn, time_interleaved  # noqa: F401

RESULTS_DIR = os.path.join("experiments", "results")
POLICY_CACHE = os.path.join("experiments", "opd_policy.pkl")

_OUT_DIR: str | None = None          # --out override, set by bench_args


def results_dir() -> str:
    return _OUT_DIR or RESULTS_DIR


def set_results_dir(path: str | None) -> None:
    """Redirect ``save_results`` (benchmarks' JSON payloads) to ``path`` —
    CI points this at an artifact dir so committed baselines in
    experiments/results/ are never clobbered by a CI run."""
    global _OUT_DIR
    _OUT_DIR = path


def bench_args(argv=None, *, description: str | None = None,
               parser: argparse.ArgumentParser | None = None):
    """The flags every benchmark script shares: ``--quick`` (CI-sized
    episode/epoch counts) and ``--out DIR`` (JSON destination). Pass a
    pre-built ``parser`` to stack script-specific flags on top. Turns on
    the persistent compilation cache (``repro.compile_cache``)."""
    ap = parser or argparse.ArgumentParser(description=description)
    ap.add_argument(
        "--quick",
        action="store_true",
        help="reduced episode/epoch counts (CI-sized)",
    )
    ap.add_argument(
        "--out",
        default=None,
        metavar="DIR",
        help=f"write JSON results here (default {RESULTS_DIR})",
    )
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.out:
        set_results_dir(args.out)
    return args


def bench_main(run, argv=None, *, parser=None, kwargs_from_args=None) -> None:
    """Shared ``__main__`` driver: parse the common flags, invoke
    ``run(quick=...)``, emit the benchmark,metric,value,reference CSV.
    Scripts with extra flags pass a pre-built ``parser`` plus
    ``kwargs_from_args(args) -> dict`` to thread them into ``run``."""
    args = bench_args(argv, parser=parser)
    kwargs = kwargs_from_args(args) if kwargs_from_args else {}
    print("benchmark,metric,value,reference")
    for r in run(quick=args.quick, **kwargs):
        print(",".join(str(x).replace(",", ";") for x in r))


def save_results(name: str, payload: dict) -> None:
    os.makedirs(results_dir(), exist_ok=True)
    with open(os.path.join(results_dir(), name + ".json"), "w") as f:
        json.dump(payload, f, indent=1, default=_np_default)


def _np_default(o):
    if isinstance(o, (np.floating, np.integer)):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(type(o))


def trained_opd(episodes: int = 36, *, seed: int = 0, force: bool = False,
                log=print, pipeline=None, cache_tag: str | None = None):
    """Train (or load cached) OPD policy on the paper's three workload
    regimes, round-robin over episodes. Returns (params, trainer_history).

    ``pipeline`` (a PipelineSpec; default the registered "paper-4stage")
    selects the pipeline — pass a cluster-bearing spec for placement-aware
    training, together with a distinct ``cache_tag`` (the policy's input
    layout grows per-node features, so caches are not interchangeable)."""
    from repro import api
    from repro.cluster import PipelineEnv
    from repro.core import OPDTrainer, PPOConfig

    cache = POLICY_CACHE if cache_tag is None else os.path.join(
        "experiments",
        f"opd_policy_{cache_tag}.pkl",
    )
    if not force and os.path.exists(cache):
        with open(cache, "rb") as f:
            blob = pickle.load(f)
        if blob.get("episodes", 0) >= episodes:
            return blob["params"], blob["history"]

    spec = pipeline or api.get_pipeline("paper-4stage")
    pipe = spec.build()
    kinds = ("steady_low", "fluctuating", "steady_high")

    def make_env(seed_):
        scen = api.get_scenario(kinds[seed_ % 3])
        return PipelineEnv(pipe, scen.train_trace(seed_), seed=seed_)

    tr = OPDTrainer(pipe, make_env, ppo=PPOConfig(expert_freq=4), seed=seed)
    for e in range(1, episodes + 1):
        tr.train_episode(e, env_seed=e)
        if log and (e % 6 == 0 or e == 1):
            log(
                f"  opd episode {e:3d}/{episodes} "
                f"reward={tr.history['reward'][-1]:9.2f} "
                f"loss={tr.history['loss'][-1]:8.4f} "
                f"expert={tr.history['expert'][-1]}"
            )
    os.makedirs(os.path.dirname(cache), exist_ok=True)
    with open(cache, "wb") as f:
        pickle.dump(
            {"params": tr.params, "history": tr.history, "episodes": episodes},
            f,
        )
    return tr.params, tr.history
