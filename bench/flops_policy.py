"""Operations of the OPD policy-and-value network, counted from shapes.

Only the matmuls against weights are counted, 2 FLOPs per multiply-add;
norms, activations, the softmax and the twin's event loop are not. A
forward over ``rows`` observations runs the projection (``state_dim`` x
``feature_dim``), two ``feature_dim``-square layers per residual block,
the heads (``feature_dim`` x each head's size) and the value head. A
backward computes each weight's gradient (as many FLOPs as its forward)
and each layer's input gradient, but not the observations' own, so the
projection's backward is half the others'.

One episode of training on the runtime twin (``core/ppo.py``) runs:
- the rollout: one forward per environment and interval, to sample the
  action, and one more per environment for the last value, whose logits
  are not needed;
- the update: per epoch the batch cut into minibatches, each a forward and
  backward over its rows and over a behaviour-cloning batch of
  ``minibatch`` rows (computed though its coefficient is 0).
"""
from __future__ import annotations


def _dims(cfg: dict) -> tuple[int, int, int, int]:
    pol = cfg["policy"]
    return pol["state_dim"], pol["feature_dim"], pol["res_blocks"], sum(pol["heads"])


def forward_flops(cfg: dict, rows: int, *, logits: bool = True) -> float:
    d_in, d, blocks, heads = _dims(cfg)
    macs = d_in * d + 2 * blocks * d * d + d * (heads if logits else 0) + d
    return 2.0 * rows * macs


def backward_flops(cfg: dict, rows: int) -> float:
    d_in, d, _, _ = _dims(cfg)
    return 2.0 * forward_flops(cfg, rows) - 2.0 * rows * d_in * d


def episode_flops(cfg: dict, n_steps: int) -> dict[str, float]:
    """FLOPs of one training episode: ``rollout``, ``update`` and ``total``."""
    envs, mb = cfg["controller"]["num_envs"], cfg["ppo"]["minibatch"]
    rows = envs * n_steps
    rollout = forward_flops(cfg, rows) + forward_flops(cfg, envs, logits=False)
    sizes = [min(mb, rows - s) for s in range(0, rows, mb)]
    update = cfg["ppo"]["epochs"] * sum(
        forward_flops(cfg, n) + backward_flops(cfg, n)
        + forward_flops(cfg, mb) + backward_flops(cfg, mb) for n in sizes)
    return {"rollout": rollout, "update": update, "total": rollout + update}
