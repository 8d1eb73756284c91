"""End-to-end LM training driver: train a ~100M-parameter model for a few
hundred steps on the synthetic Markov-automaton corpus and watch the loss
fall well below log(V).

    PYTHONPATH=src python examples/train_lm.py [--arch xlstm-125m] [--steps 300]

Uses the very same make_train_step / sharding code path the multi-pod
dry-run compiles for the 512-chip mesh — here on the local device(s).
"""
import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs import ARCHS
from repro.data.tokens import synthetic_lm_batches
from repro.models import api, steps
from repro.train import adamw_init

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="xlstm-125m", choices=sorted(ARCHS))
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--seq-len", type=int, default=256)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--d-model", type=int, default=None,
                help="override width (default: ~100M-param reduction)")
args = ap.parse_args()
compile_cache.enable()

base = ARCHS[args.arch]
# reduce to ~100M params for a CPU-trainable run, keep the family intact
cfg = base.replace(n_layers=min(base.n_layers, 8),
                   d_model=args.d_model or min(base.d_model, 512),
                   n_heads=min(base.n_heads, 8),
                   n_kv=min(base.n_kv, 8),
                   d_ff=min(base.d_ff, 2048) if base.d_ff else 0,
                   n_experts=min(base.n_experts, 4) if base.n_experts else 0,
                   vocab=min(base.vocab, 32768))
print(f"{cfg.name}: {cfg.param_count() / 1e6:.1f}M params "
      f"({cfg.active_param_count() / 1e6:.1f}M active), vocab={cfg.vocab}")

params = api.init_model(jax.random.PRNGKey(0), cfg)
opt = adamw_init(params)
train = jax.jit(steps.make_train_step(cfg, lr=1e-3))
data = synthetic_lm_batches(vocab=cfg.vocab, seq_len=args.seq_len,
                            batch=args.batch, seed=0)

log_v = float(np.log(cfg.vocab))
print(f"uniform-token floor: log(V) = {log_v:.3f}")
t0 = time.time()
first = None
for step in range(1, args.steps + 1):
    batch = {k: jax.numpy.asarray(v) for k, v in next(data).items()}
    params, opt, metrics = train(params, opt, batch)
    if step == 1:
        first = float(metrics["loss"])
    if step % 20 == 0 or step == 1:
        print(f"step {step:4d}  loss={float(metrics['loss']):7.4f}  "
              f"grad_norm={float(metrics['grad_norm']):8.3f}  "
              f"{(time.time() - t0) / step:5.2f}s/step")

final = float(metrics["loss"])
print(f"\nloss {first:.3f} -> {final:.3f} "
      f"({'below' if final < log_v else 'NOT below'} log V = {log_v:.3f})")
assert final < first, "loss must decrease"
