"""Serving launcher: batched single-token decode against a KV cache — the
data plane the OPD controller manages — plus the event-driven pipeline mode.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        [--batch 4] [--context 128] [--tokens 32]

    PYTHONPATH=src python -m repro.launch.serve --pipeline \
        [--scenario bursty] [--horizon 120] [--policy greedy] [--seed 3] \
        [--cluster edge-hetero-3]

    PYTHONPATH=src python -m repro.launch.serve \
        --fleet fleet-3tenant-hetero [--horizon 120]

Single-arch mode runs prefill once to populate the cache, then streams
decode steps; on TPU the same serve_step is what launch/dryrun.py compiles
for the decode_32k / long_500k shapes of the production mesh. ``--pipeline``
instead serves an arrival scenario through the event-driven runtime with any
registered controller in the loop (``--policy opd`` trains the agent first),
printing per-interval telemetry. ``--fleet`` serves a registered multi-tenant
fleet (N pipelines on one shared cluster and event loop) and prints the
per-tenant shed / latency summary. Everything is built from ``repro.api``
specs, so the run is reproducible from its seeds.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import ARCHS
from repro.models import api


def _ms(v) -> str:
    """Milliseconds formatter, null-safe (summary emits None when nothing
    completed)."""
    return "n/a" if v is None else f"{v * 1e3:.0f}ms"


def run_pipeline(args):
    from repro import api

    pipeline = api.get_pipeline("serve2")
    if args.cluster:
        # place the pipeline on a registered (possibly heterogeneous)
        # cluster topology instead of the homogeneous scalar pool
        pipeline = api.replace(pipeline, cluster=api.get_cluster(args.cluster))
    exp = api.ExperimentSpec(
        pipeline=pipeline,
        scenario=api.replace(api.get_scenario(args.scenario), rate=args.rate,
                             seed=args.seed, horizon=args.horizon),
        controller=api.replace(api.get_controller(args.policy),
                               seed=args.seed))
    sess = api.Session.from_spec(exp)
    sess.train(log=print)

    def show(env, cfg, info):
        line = (f"t={env.runtime.now:5.0f}s z={cfg.z} f={cfg.f} b={cfg.b} "
                f"demand={info['demand']:5.1f}/s served={info['processed']:4d} "
                f"p95={info['p95'] * 1e3:7.1f}ms backlog={info['backlog']}")
        if args.cluster:
            line += (" nodes=" + "/".join(f"{u:.2f}"
                                          for u in info["node_utilization"])
                     + f" migrations={info['migrations']}")
        print(line)

    s = sess.serve(on_step=show)["summary"]
    print(f"served {s['served']} requests ({s['throughput_rps']:.1f} req/s) "
          f"p50={_ms(s['p50'])} p95={_ms(s['p95'])} p99={_ms(s['p99'])}")
    if args.cluster:
        print(f"cluster {args.cluster}: "
              f"{s['migrations']} replica migrations, node utilization "
              + " ".join(f"{u:.2f}" for u in s.get("node_utilization", [])))


def run_fleet(args):
    from repro import api

    spec = api.get_fleet(args.fleet)
    sess = api.FleetSession.from_spec(spec)

    def show(fleet, interval):
        now = fleet.loop.now
        for name, info in interval.items():
            print(f"t={now:5.0f}s {name:<12} demand={info['demand']:5.1f}/s "
                  f"served={info['processed']:4d} shed={info['shed']:3d} "
                  f"p95={_ms(info['p95'] if info['p95'] == info['p95'] else None)}"
                  f" backlog={info['backlog']}")

    rep = sess.serve(horizon=args.horizon, on_step=show)
    s = rep["summary"]
    for name, t in s["tenants"].items():
        line = (f"tenant {name:<12} prio={t['priority']} "
                f"share={t['share']:.2f} offered={t['arrived']:6d} "
                f"served={t['served']:6d} shed={t['shed']:5d} "
                f"({t['shed_rate'] * 100:.1f}%) p50={_ms(t['p50'])} "
                f"p95={_ms(t['p95'])} p99={_ms(t['p99'])}")
        if "slo_p99" in t:
            line += (f" slo_p99={_ms(t['slo_p99'])} "
                     f"{'MET' if t['slo_p99_met'] else 'MISSED'}")
        print(line)
    f = s["fleet"]
    print(f"fleet {spec.name}: {f['tenants']} tenants, "
          f"{f['served']}/{f['offered']} served "
          f"(shed {f['shed']}, {f['shed_rate'] * 100:.1f}%), "
          f"{f['events']} events ({f['events_per_s']:.0f}/s), "
          f"{f['reallocations']} reallocations")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=sorted(ARCHS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--context", type=int, default=128)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--pipeline", action="store_true",
                    help="serve an arrival scenario through the event-driven "
                         "pipeline runtime instead of single-arch decode")
    from repro.api import (list_clusters, list_controllers, list_fleets,
                           list_scenarios)
    ap.add_argument("--scenario", default="bursty", choices=list_scenarios())
    ap.add_argument("--policy", default="greedy", choices=list_controllers())
    ap.add_argument("--cluster", default=None, choices=list_clusters(),
                    help="place the pipeline on a registered cluster "
                         "topology (default: homogeneous scalar pool)")
    ap.add_argument("--fleet", default=None, choices=list_fleets(),
                    help="serve a registered multi-tenant fleet (N pipelines "
                         "on one shared cluster and event loop)")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--horizon", type=int, default=120)
    ap.add_argument("--rate", type=float, default=25.0)
    args = ap.parse_args()
    compile_cache.enable()

    if args.fleet:
        return run_fleet(args)
    if args.pipeline:
        return run_pipeline(args)

    cfg = ARCHS[args.arch].smoke() if args.smoke else ARCHS[args.arch]
    if cfg.enc_len:
        raise SystemExit("use whisper decode via models.api directly; the "
                         "serve launcher drives decoder-only archs")
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    B = args.batch

    cache = api.init_cache(cfg, B, args.context)
    decode = jax.jit(lambda p, b, c: api.decode_step(p, b, c, cfg),
                     donate_argnums=(2,))

    tok = jnp.asarray(rng.integers(1, cfg.vocab, (B, 1)), dtype=jnp.int32)
    out_tokens = []
    t0 = time.time()
    for i in range(args.tokens):
        logits, cache = decode(params, {"tokens": tok}, cache)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok)[:, 0])
        if i == 0:
            print(f"first token (incl. compile): {time.time() - t0:.2f}s")
    dt = time.time() - t0
    toks = B * args.tokens
    print(f"decoded {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, batch {B})")
    print("sample:", np.stack(out_tokens, 1)[0][:16])


if __name__ == "__main__":
    main()
