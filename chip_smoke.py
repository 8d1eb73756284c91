"""Proof that the system's main path runs on a TPU, at published widths.

    python chip_smoke.py              # one chip: device, twin, serve, stage step
    python chip_smoke.py --chips 4    # four chips: granite-3-8b over a (1, 4) mesh

One process holds the chip from its first JAX call to its exit. Each phase
prints one line saying what it did and how many seconds it took, compile
included; a failed check raises, so the script exits non-zero and prints no
result. The last line of a run that passed is one JSON object naming the
device as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (one chip):
  device      JAX's first device must be a TPU; there is no CPU fallback.
  twin        PPO-trains the OPD controller for one episode on serve2, the
              rollouts done by the jitted runtime twin (core/runtime_vec.py).
  serve       serves serve2 with that controller through the event-driven
              runtime, every stage a live model at its published widths in
              bf16, and checks that every offered request was served.
  stage step  starcoder2-3b's decode step through StageExecutor at batch 8,
              once with jnp attention and once with the Pallas decode kernel
              compiled by Mosaic, and compares their logits.
With ``--chips 4`` the one phase after the device check runs granite-3-8b's
decode step at full depth over a (1, 4) mesh — the stage does not fit one
chip — and checks a depth-cut granite-3-8b on that mesh against the same
model on one device.

The phase functions take their sizes as arguments, so the CPU tests run
them at smoke widths.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import api, compat, compile_cache  # noqa: E402
from repro.cluster.executor import StageExecutor  # noqa: E402

# Two computations of the same bf16 model are compared by the relative RMS
# error of their logits, ||a - b|| / ||b||. Both hold every activation in
# bf16 (unit roundoff u = 2**-8) and differ only in the order of their f32
# sums: online vs. full softmax in the stage step, per-device partial sums
# in the sharded check. That flips the last bit of some activations in
# every layer, and the flips walk through the residual stream: on the CPU,
# 16 to 40 bf16 layers at widths 1024 to 2048 measured 1.8 % to 2.7 %,
# about u * sqrt(layers). 8e-2 leaves three times that at 40 layers and
# still rejects a wrong mask, head mapping or shard, each of which moves
# the logits by tens of percent.
LOGIT_RTOL = 8e-2


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def report(phase: str, seconds: float, what: str) -> None:
    print(f"[{phase}] {what} ({seconds:.1f} s)", flush=True)


def phase_device(chips: int) -> dict:
    """The device JAX found; exits non-zero unless it is a TPU with at
    least ``chips`` devices."""
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found platform "
                         f"{d.platform!r} ({d.device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke --chips {chips} found only "
                         f"{len(devices)} {d.device_kind} device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def serve_spec(*, widths: str, rate: float = 1.0,
               horizon: int = 40) -> api.ExperimentSpec:
    """serve2 under a low Poisson rate over a short horizon (a few dozen
    requests, so batches stay small and few shapes compile), controlled by
    OPD trained for one episode on the runtime twin."""
    return api.ExperimentSpec(
        pipeline=api.get_pipeline("serve2"),
        scenario=api.ScenarioSpec(kind="poisson", rate=rate, seed=0,
                                  horizon=horizon),
        controller=api.replace(api.get_controller("opd"), train_episodes=1,
                               train_backend="runtime"),
        backend="runtime", real=True, widths=widths)


def phase_twin(spec: api.ExperimentSpec) -> tuple[api.Session, str]:
    sess = api.Session.from_spec(spec)
    sess.train()
    h = sess.trainer.history
    check(not h["expert"][-1], "episode 1 must roll on the twin, not the "
          "expert's Python env")
    check(np.isfinite(h["reward"][-1]) and np.isfinite(h["loss"][-1]),
          f"non-finite PPO episode: {h}")
    c = spec.controller
    return sess, (f"OPD trained {c.train_episodes} episode on "
                  f"{spec.pipeline.name}, {c.num_envs} envs rolled by the "
                  f"runtime twin: reward={h['reward'][-1]:.3f} "
                  f"loss={h['loss'][-1]:.4f}")


def phase_serve(sess: api.Session) -> tuple[dict, str]:
    s = sess.serve()["summary"]
    check(s["arrived"] > 0, "no request arrived")
    check(s["served"] == s["arrived"] == s["submitted"],
          f"served {s['served']} of {s['arrived']} offered requests")
    stages = []
    for st in s["executors"]:
        ran = ", ".join(f"{name} {v['dtype']} x{v['batches']}"
                        for name, v in st["executed"].items())
        stages.append(f"{st['stage']}: {ran}; {st['compiled_shapes']} "
                      f"shapes compiled in {st['compile_s']:.1f} s")
    check(all(st["executed"] for st in s["executors"]),
          "a stage executed no batch")
    return s, (f"served == offered == {s['served']} requests "
               f"({sess.spec.widths} widths) | " + " | ".join(stages))


def decode_logits(ex: StageExecutor, arch: str, batch: int, steps: int,
                  backend: str = "reference"):
    """Logits of ``steps`` decode steps from an empty cache, the same
    tokens fed at every step; -> (logits [steps, B, 1, V] f32, entry)."""
    entry, (params, batch_in, cache), _ = ex.compiled_step(
        arch, batch, backend=backend)
    out = []
    for _ in range(steps):
        logits, cache = entry.compiled(params, batch_in, cache)
        out.append(np.asarray(logits, dtype=np.float32))
    return np.stack(out), entry


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def phase_stage_step(*, smoke: bool, arch: str = "starcoder2-3b",
                     batch: int = 8, seq_len: int = 1024,
                     steps: int = 8) -> tuple[dict, str]:
    ex = StageExecutor(compat.make_mesh((1, 1), ("data", "model")),
                       seq_len=seq_len, smoke=smoke)
    ref, ref_e = decode_logits(ex, arch, batch, steps, "reference")
    fl, fl_e = decode_logits(ex, arch, batch, steps, "flash")
    check(np.isfinite(ref).all() and np.isfinite(fl).all(),
          "non-finite logits")
    err = rel_err(fl, ref)
    check(err <= LOGIT_RTOL, f"flash vs reference logits: relative error "
          f"{err:.3g} > {LOGIT_RTOL}")
    mosaic = "tpu_custom_call" in fl_e.compiled.as_text()
    cfg = ex.arch_config(arch)
    out = {"rel_err": err, "mosaic": mosaic,
           "compile_s": ref_e.compile_s + fl_e.compile_s}
    return out, (f"{arch} {cfg.dtype} {cfg.n_layers}L d={cfg.d_model} "
                 f"batch {batch}, cache {seq_len}, {steps} decode steps: "
                 f"flash vs reference logits rel err {err:.3g} <= "
                 f"{LOGIT_RTOL}; Mosaic kernel in the flash step: {mosaic}; "
                 f"compiled 2 steps in {out['compile_s']:.1f} s")


def phase_sharded_stage(*, smoke: bool, arch: str = "granite-3-8b",
                        batch: int = 8, cut_layers: int = 16,
                        steps: int = 4) -> tuple[dict, str]:
    """``arch`` at full depth over a (1, n) mesh of every local device,
    then the depth-cut model on that mesh against one device."""
    n = len(jax.devices())
    wide = compat.make_mesh((1, n), ("data", "model"))
    full_ex = StageExecutor(wide, smoke=smoke)
    full, _ = decode_logits(full_ex, arch, batch, 1)
    check(np.isfinite(full).all(), "non-finite full-depth logits")
    full_cfg = full_ex.arch_config(arch)
    del full_ex                           # free the full model's shards
    cut_wide = StageExecutor(wide, smoke=smoke, n_layers=cut_layers)
    sharded, _ = decode_logits(cut_wide, arch, batch, steps)
    del cut_wide
    one = StageExecutor(compat.make_mesh((1, 1), ("data", "model")),
                        smoke=smoke, n_layers=cut_layers)
    single, _ = decode_logits(one, arch, batch, steps)
    err = rel_err(sharded, single)
    check(err <= LOGIT_RTOL, f"(1, {n}) mesh vs one device: relative "
          f"error {err:.3g} > {LOGIT_RTOL}")
    return {"rel_err": err, "model_axis": n}, (
        f"{arch} {full_cfg.n_layers}L d={full_cfg.d_model} "
        f"({full_cfg.param_count() / 1e9:.1f} B params, bf16) decode step "
        f"on a (1, {n}) mesh: logits {full.shape[1:]} finite; "
        f"{cut_layers}-layer cut on (1, {n}) vs one device over {steps} "
        f"steps: rel err {err:.3g} <= {LOGIT_RTOL}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded granite-3-8b stage phase")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    device = phase_device(args.chips)
    cache_dir = compile_cache.enable()
    report("device", time.perf_counter() - t0,
           f"{device['count']} x {device['kind']} ({device['platform']}); "
           f"compile cache {cache_dir}")

    if args.chips == 4:
        t0 = time.perf_counter()
        _, what = phase_sharded_stage(smoke=False)
        report("sharded stage", time.perf_counter() - t0, what)
    else:
        t0 = time.perf_counter()
        sess, what = phase_twin(serve_spec(widths="published"))
        report("twin", time.perf_counter() - t0, what)

        t0 = time.perf_counter()
        _, what = phase_serve(sess)
        report("serve", time.perf_counter() - t0, what)
        del sess                          # release the served stages' weights

        t0 = time.perf_counter()
        out, what = phase_stage_step(smoke=False)
        check(out["mosaic"], "the flash step holds no Mosaic kernel: the "
              "decode kernel ran in the Pallas interpreter")
        report("stage step", time.perf_counter() - t0, what)

    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
