import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh with 512 placeholder host devices, and extract the
memory / cost / collective figures that feed §Dry-run and §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out D]

The XLA_FLAGS line above MUST run before any jax import (device count locks
on first jax init) — keep these the first two statements of the module.
"""

import argparse
import json
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS
from repro.distributed import sharding as shd
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.models import api, steps
from repro.models.config import INPUT_SHAPES
from repro.train import adamw_init

# --------------------------------------------------------- hw constants ----
PEAK_FLOPS = 197e12     # bf16 / chip (v5e)
HBM_BW = 819e9          # bytes/s / chip
LINK_BW = 50e9          # bytes/s / ICI link

SKIPS = {
    # enc-dec with 448 target positions has no 500k-decode regime (DESIGN.md)
    ("whisper-small", "long_500k"): "enc-dec: no 500k decode regime",
}

def model_flops(cfg, shape) -> float:
    """6·N_active·D for train, 2·N_active·D for inference FLOPs/step."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * 1 * shape.global_batch           # decode: one token


def build_inputs(cfg, shape, mesh, *, multi_pod: bool):
    """(abstract args, in_shardings, step_fn) for one (arch, shape)."""
    bs = steps.batch_specs(cfg, shape)
    bsh = shd.batch_shardings(cfg, shape, mesh, multi_pod=multi_pod)
    psh = shd.param_shardings(cfg, mesh, multi_pod=multi_pod, kind=shape.kind)
    params_shape = jax.eval_shape(lambda k: api.init_model(k, cfg),
                                  jax.random.PRNGKey(0))
    if shape.kind == "train":
        shard_h = shd.residual_constraint(cfg, shape, mesh, multi_pod=multi_pod)
        # Gradient accumulation (make_train_step(microbatch=...)) was
        # measured OFF here: under GSPMD the grad all-reduce fires once per
        # microbatch (deepseek coll 24 -> 86 s at mb=4) and FSDP weights are
        # re-gathered per microbatch (llama4 coll 13 -> 35 s). It remains a
        # launcher option for memory-constrained real runs.
        mb = None
        step = steps.make_train_step(cfg, shard_h=shard_h, microbatch=mb)
        opt_shape = jax.eval_shape(adamw_init, params_shape)
        zsh = shd.opt_shardings(cfg, mesh, multi_pod=multi_pod)  # ZeRO-1
        osh = {"m": zsh, "v": zsh, "step": NamedSharding(mesh, P())}
        # params and opt state are updated in place on real hardware
        return (params_shape, opt_shape, bs), (psh, osh, bsh), step, (0, 1)
    if shape.kind == "prefill":
        shard_h = shd.residual_constraint(cfg, shape, mesh, multi_pod=multi_pod)
        step = steps.make_prefill_step(cfg, shard_h=shard_h)
        return (params_shape, bs), (psh, bsh), step, ()
    cache = steps.cache_specs(cfg, shape)
    csh = shd.cache_shardings(cfg, shape, mesh, multi_pod=multi_pod)
    step = steps.make_serve_step(cfg, shape)
    # the KV/state cache is donated: decode updates it in place
    return (params_shape, bs, cache), (psh, bsh, csh), step, (2,)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               verbose: bool = True) -> dict:
    shape = INPUT_SHAPES[shape_name]
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "SKIP", "reason": SKIPS[(arch, shape_name)]}
    cfg = ARCHS[arch].replace(dtype="bfloat16")
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v

    args, in_sh, step, donate = build_inputs(cfg, shape, mesh,
                                             multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=in_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-aware costs (XLA's cost_analysis counts scan bodies once)
    hc = hlo_cost.analyze(hlo)
    coll = hc["collectives"]
    coll_bytes = hc["collective_bytes"]

    flops_dev = float(hc["flops"])
    bytes_dev = float(hc["bytes"])
    mf = model_flops(cfg, shape)
    terms = {
        # per-chip seconds (cost_analysis is the per-device SPMD program)
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_bytes / LINK_BW,
    }
    dominant = max(terms, key=terms.get)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "OK",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_bytes,
        "collectives": coll,
        "xla_cost_analysis": {"flops_body_once": float(ca.get("flops", 0.0)),
                              "bytes_body_once": float(ca.get("bytes accessed", 0.0))},
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": (ma.argument_size_in_bytes + ma.temp_size_in_bytes),
        },
        "roofline": {**{k: float(v) for k, v in terms.items()},
                     "dominant": dominant},
        "model_flops_global": mf,
        "model_flops_per_device": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops_dev if flops_dev else 0.0,
    }
    if verbose:
        mem_gb = rec["memory"]["peak_bytes"] / 1e9
        print(f"{arch:26s} {shape_name:12s} {rec['mesh']:8s} "
              f"compile={t_compile:6.1f}s mem={mem_gb:7.2f}GB "
              f"comp={terms['compute_s']*1e3:8.2f}ms "
              f"mem_t={terms['memory_s']*1e3:8.2f}ms "
              f"coll={terms['collective_s']*1e3:8.2f}ms -> {dominant}"
              f" useful={rec['useful_flops_ratio']:.2f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = list(ARCHS) if args.all or args.arch is None else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"{tag}: cached")
                    continue
                try:
                    rec = dryrun_one(arch, shape, multi_pod=mp)
                except Exception as e:  # noqa: BLE001 — report and continue
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "FAIL", "error": str(e)[:2000]}
                    failures.append(tag)
                    print(f"{tag}: FAIL {str(e)[:200]}")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
