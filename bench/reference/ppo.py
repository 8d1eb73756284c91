"""A plain PPO update of the OPD policy, for the comparison that decides
``correct`` in a training cell.

Written from the paper's Eqs. 11-12 and standard PPO with generalised
advantage estimation, global-norm gradient clipping and AdamW, with the
sizes and coefficients the configuration file states. It imports nothing
of the program and takes nothing the program made but the rollout's
actions and rewards, as a served model's check takes its served tokens;
the observations come from ``bench/reference/twin.py`` and the
log-probabilities and values from the reference's own policy:

- the policy: a linear projection to ``feature_dim`` and ReLU, then
  ``res_blocks`` residual blocks (LayerNorm, linear, ReLU, linear, added
  back), then one linear head per knob (categorical logits) and a linear
  value head; weights drawn from the seed as the configuration's ``init``
  states (LeCun-normal, heads and value scaled by ``head_init_scale``,
  biases zero, LayerNorm gain one);
- advantages: GAE over each environment's episode on the rewards times
  ``reward_scale``, then normalised over the whole batch;
- the loss (Eq. 11): the clipped surrogate (Eq. 12) plus ``c1`` times the
  value error minus ``c2`` times the entropy;
- the step: gradients clipped to a global norm, then AdamW with bias
  correction.

``precision`` names how it computes: ``"highest"``, float32 with every
matmul at ``jax.default_matmul_precision("highest")``; two controls, the
reference in lower precision: ``"bfloat16"``, the parameters,
activations, gradients and optimizer state all bfloat16; ``"int8"``,
float32 state with every matmul of the forward and the backward computed
in int8 (each operand rounded to int8 along the contracted axis, one
absmax scale per slice, the products summed exactly, as
``bench/reference/transformer.py``'s control does).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def init_params(cfg: dict, seed: int) -> dict[str, jax.Array]:
    """The policy's initial weights, by path, drawn from ``seed``."""
    pol = cfg["policy"]
    d_in, d, heads = pol["state_dim"], pol["feature_dim"], pol["heads"]
    ks = jax.random.split(jax.random.PRNGKey(seed), len(heads) + 2)
    fk = jax.random.split(ks[0], pol["res_blocks"] + 1)

    def lecun(key, n_in, n_out, scale=None):
        scale = 1.0 / n_in ** 0.5 if scale is None else scale
        return jax.random.normal(key, (n_in, n_out), jnp.float32) * scale

    p = {"features/proj/w": lecun(fk[0], d_in, d),
         "features/proj/b": jnp.zeros(d, jnp.float32)}
    for i, k in enumerate(fk[1:]):
        k1, k2 = jax.random.split(k)
        pre = f"features/blocks/{i}"
        p[f"{pre}/ln/g"] = jnp.ones(d, jnp.float32)
        p[f"{pre}/ln/b"] = jnp.zeros(d, jnp.float32)
        p[f"{pre}/fc1/w"] = lecun(k1, d, d)
        p[f"{pre}/fc1/b"] = jnp.zeros(d, jnp.float32)
        p[f"{pre}/fc2/w"] = lecun(k2, d, d)
        p[f"{pre}/fc2/b"] = jnp.zeros(d, jnp.float32)
    scale = pol["head_init_scale"]
    for i, (k, n) in enumerate(zip(ks[1:-1], heads, strict=True)):
        p[f"heads/{i}/w"] = lecun(k, d, n, scale)
        p[f"heads/{i}/b"] = jnp.zeros(n, jnp.float32)
    p["value/w"] = lecun(ks[-1], d, 1, scale)
    p["value/b"] = jnp.zeros(1, jnp.float32)
    return p


HIGHEST = jax.lax.Precision.HIGHEST
DTYPES = {"highest": jnp.float32, "bfloat16": jnp.bfloat16, "int8": jnp.float32}


def _int8(x, axis: int):
    """x rounded to int8 along ``axis`` (one absmax scale per slice), back
    in float32: what an int8 matmul operand holds."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    return jnp.clip(jnp.round(x / jnp.where(scale > 0, scale, 1.0)), -127, 127) * scale


@jax.custom_vjp
def _mm_int8(x, w):
    """x [B, in] @ w [in, out] in int8; its gradients in int8 too."""
    return jnp.matmul(_int8(x, 1), _int8(w, 0), precision=HIGHEST)


def _mm_int8_fwd(x, w):
    return _mm_int8(x, w), (x, w)


def _mm_int8_bwd(res, g):
    x, w = res
    return (jnp.matmul(_int8(g, 1), _int8(w, 1).T, precision=HIGHEST),
            jnp.matmul(_int8(x, 0).T, _int8(g, 0), precision=HIGHEST))


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def forward(p: dict, x: jax.Array, n_blocks: int, n_heads: int, eps: float,
            precision: str = "highest"):
    """Observations [B, D] -> (logits per head, value [B])."""
    mm = _mm_int8 if precision == "int8" else jnp.matmul
    h = jax.nn.relu(mm(x, p["features/proj/w"]) + p["features/proj/b"])
    for i in range(n_blocks):
        pre = f"features/blocks/{i}"
        mu = h.mean(-1, keepdims=True)
        var = ((h - mu) ** 2).mean(-1, keepdims=True)
        y = (h - mu) / jnp.sqrt(var + eps) * p[f"{pre}/ln/g"] + p[f"{pre}/ln/b"]
        y = jax.nn.relu(mm(y, p[f"{pre}/fc1/w"]) + p[f"{pre}/fc1/b"])
        h = h + (mm(y, p[f"{pre}/fc2/w"]) + p[f"{pre}/fc2/b"])
    logits = [mm(h, p[f"heads/{i}/w"]) + p[f"heads/{i}/b"] for i in range(n_heads)]
    return logits, (mm(h, p["value/w"]) + p["value/b"])[:, 0]


def _logp_entropy(logits, actions):
    logp = ent = 0.0
    for i, lg in enumerate(logits):
        ls = jax.nn.log_softmax(lg)
        logp = logp + jnp.take_along_axis(ls, actions[:, i:i + 1], -1)[:, 0]
        ent = ent - jnp.sum(jnp.exp(ls) * ls, -1)
    return logp, ent


def policy_outputs(cfg: dict, params: dict, states, actions, last_states):
    """What the rollout records beside its actions, from the reference's
    own policy in float32 at ``highest``: the log-probability of each
    action and the value of each state ([E, T] each, ``states`` [E, T, D],
    ``actions`` [E, T, heads]) and each environment's value of its state
    after the last decision ([E], ``last_states`` [E, D])."""
    pol = cfg["policy"]
    nb, nh, eps = pol["res_blocks"], len(pol["heads"]), pol["layernorm_eps"]
    states = jnp.asarray(states, jnp.float32)
    E, T, D = states.shape
    with jax.default_matmul_precision("highest"):
        logits, values = forward(params, states.reshape(E * T, D), nb, nh, eps)
        logp, _ = _logp_entropy(logits, jnp.asarray(actions).reshape(E * T, -1))
        _, last = forward(params, jnp.asarray(last_states, jnp.float32), nb, nh, eps)
    return (np.asarray(logp).reshape(E, T), np.asarray(values).reshape(E, T),
            np.asarray(last))


def advantages(cfg: dict, rewards, values, last_value):
    """GAE per environment ([E, T] rewards and values, [E] bootstrap), in
    float64; returns (normalised advantages, returns), flattened env-major
    to [E * T], in float32."""
    ppo = cfg["ppo"]
    r = np.asarray(rewards, np.float64) * ppo["reward_scale"]
    v = np.asarray(values, np.float64)
    nxt = np.asarray(last_value, np.float64)
    adv = np.zeros_like(r)
    gae = np.zeros(r.shape[0])
    for t in reversed(range(r.shape[1])):
        delta = r[:, t] + ppo["gamma"] * nxt - v[:, t]
        gae = delta + ppo["gamma"] * ppo["gae_lambda"] * gae
        adv[:, t] = gae
        nxt = v[:, t]
    ret = (adv + v).reshape(-1)
    adv = adv.reshape(-1)
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    return adv.astype(np.float32), ret.astype(np.float32)


def minibatches(cfg: dict, seed: int, rows: int, steps: int) -> list[np.ndarray]:
    """The rows of the first ``steps`` minibatches: each epoch a new
    permutation of the batch from one generator seeded with ``seed``, cut
    into ``minibatch``-sized pieces."""
    mb = cfg["ppo"]["minibatch"]
    rng = np.random.default_rng(seed)
    out: list[np.ndarray] = []
    while len(out) < steps:
        idx = rng.permutation(rows)
        out += [idx[s:s + mb] for s in range(0, rows, mb)]
    return out[:steps]


def _step(cfg: dict, p, m, v, t, batch, precision):
    ppo, pol = cfg["ppo"], cfg["policy"]
    opt = ppo["optimizer"]
    states, actions, old_logp, adv, returns = batch
    nb, nh = pol["res_blocks"], len(pol["heads"])
    dtype = DTYPES[precision]

    def loss_fn(q):
        logits, value = forward(q, states.astype(dtype), nb, nh, pol["layernorm_eps"],
                                precision)
        logp, ent = _logp_entropy(logits, actions)
        ratio = jnp.exp(logp - old_logp.astype(dtype))
        a = adv.astype(dtype)
        clipped = jnp.clip(ratio, 1 - ppo["clip_eps"], 1 + ppo["clip_eps"])
        l_clip = -jnp.mean(jnp.minimum(ratio * a, clipped * a))
        l_vf = jnp.mean((value - returns.astype(dtype)) ** 2)
        l_ent = jnp.mean(ent)
        return l_clip + ppo["c1"] * l_vf - ppo["c2"] * l_ent, (l_clip, l_vf, l_ent)

    (loss, terms), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    scale = jnp.minimum(1.0, opt["clip_global_norm"] / (norm + 1e-9)).astype(dtype)
    g = {k: x * scale for k, x in g.items()}
    b1, b2 = opt["b1"], opt["b2"]
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in p}
    v = {k: b2 * v[k] + (1 - b2) * g[k] * g[k] for k in p}
    c1, c2 = (1 - b1 ** t).astype(dtype), (1 - b2 ** t).astype(dtype)
    p = {k: (p[k] - ppo["lr"] * ((m[k] / c1) / (jnp.sqrt(v[k] / c2) + opt["eps"])
                                 + opt["weight_decay"] * p[k])).astype(dtype) for k in p}
    return p, m, v, loss, terms, g


def run(cfg: dict, params: dict, batches: list, *, precision: str = "highest") -> dict:
    """The first ``len(batches)`` steps from ``params`` and fresh optimizer
    state. Each batch is (states, actions, old log-probabilities,
    advantages, returns). Returns the losses (with their three terms) and
    the clipped first gradient, and the parameters after each step, all in
    float32."""
    step = jax.jit(partial(_step, cfg, precision=precision))
    p = {k: x.astype(DTYPES[precision]) for k, x in params.items()}
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    out = {"loss": [], "terms": [], "params": [], "start": p}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, start=1):
            p, m, v, loss, terms, g = step(p, m, v, jnp.float32(t),
                                           tuple(jnp.asarray(x) for x in batch))
            out["loss"].append(float(loss))
            out["terms"].append([float(x) for x in terms])
            out["params"].append(p)
            if t == 1:
                out["grad"] = g
    f32 = partial(jax.tree.map, lambda x: np.asarray(x, np.float32))
    return {**out, "start": f32(out["start"]), "params": [f32(q) for q in out["params"]],
            "grad": f32(out["grad"])}
