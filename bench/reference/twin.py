"""A plain discrete-event replay of the serving runtime, for the
comparison that decides ``correct`` in a training cell.

Written from the semantics that ``serving/runtime.py`` documents and the
configuration file states, in float64, one event at a time; it reads the
configuration file's tables and nothing of the program:

- requests arrive at stage 0; each stage keeps one FIFO queue;
- a stage dispatches a batch when it has a free replica, is not blocked
  by a cold start, and either ``b`` requests wait or the oldest has
  waited ``max_wait``; the batch takes up to ``b`` requests;
- the batch goes to the fastest free replica (ties: the lowest index) and
  takes ``(alpha + beta n) / speed`` seconds, ``speed`` that of the node
  the replica was placed on;
- a finished batch goes on to the next stage's queue, ``hop_latency``
  later where the two stages' primary nodes differ; at the last stage its
  requests are done;
- every ``adaptation_interval_s`` the next configuration (z, f, b) takes
  effect: a stage whose variant changes is blocked for ``cold_start_s``;
  replicas are placed first-fit (stages in order, replicas one at a time,
  the first node with room, else the node with the most room, the
  shortfall counted as overflow); batches in flight keep their replica;
- each interval is scored from what was measured in it (Eq. 3 and 7):
  arrivals, completions, their mean latency (the closed-form latency
  where none completed), accuracy, cost, the largest batch, and a
  penalty where the placement overflows;
- before each decision the controller observes (Eq. 5) the configuration
  in effect and the load: the arrivals of the last whole second (before
  the first decision, the mix's rate at second 0). The observation needs
  no event: ``observations`` computes it from the actions and arrivals.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass

import numpy as np


@dataclass
class Tables:
    """What the configuration file states, per stage and variant."""
    accuracy: list[list[float]]
    cost: list[list[float]]
    resource: list[list[float]]
    alpha: list[list[float]]
    beta: list[list[float]]
    batch_choices: list[int]
    f_max: float
    b_max: float
    w_max: float
    node_capacity: list[float]
    node_speed: list[float]
    hop: float
    interval: float
    cold_start: float
    max_wait: float
    penalty: float
    w: dict

    @classmethod
    def from_config(cls, cfg: dict) -> Tables:
        def col(key):
            return [[float(v[key]) for v in s["variants"]] for s in cfg["stages"]]
        rt = cfg["runtime"]
        return cls(accuracy=col("accuracy"), cost=col("cost"),
                   resource=col("resource"), alpha=col("alpha"), beta=col("beta"),
                   batch_choices=list(cfg["knobs"]["batch_choices"]),
                   f_max=float(cfg["knobs"]["f_max"]),
                   b_max=float(cfg["knobs"]["b_max"]),
                   w_max=float(cfg["knobs"]["w_max"]),
                   node_capacity=[float(n["capacity"]) for n in cfg["cluster"]["nodes"]],
                   node_speed=[float(n["speed"]) for n in cfg["cluster"]["nodes"]],
                   hop=float(cfg["cluster"]["hop_latency"]),
                   interval=float(rt["adaptation_interval_s"]),
                   cold_start=float(rt["cold_start_s"]),
                   max_wait=float(rt["max_wait_s"]),
                   penalty=float(rt["infeasible_penalty"]),
                   w=dict(cfg["qos_weights"]))


def decode(t: Tables, action) -> tuple[list[int], list[int], list[int]]:
    """Head indices [3N] -> per-stage variant, replicas and batch size."""
    n = len(t.alpha)
    z = [int(action[3 * i]) % len(t.alpha[i]) for i in range(n)]
    f = [int(action[3 * i + 1]) + 1 for i in range(n)]
    b = [t.batch_choices[int(action[3 * i + 2]) % len(t.batch_choices)]
         for i in range(n)]
    return z, f, b


def place(t: Tables, z: list[int], f: list[int]):
    """First-fit placement: per stage the node of each replica, the
    stage's primary node (most replicas, ties lowest), the overflow, and
    each node's capacity left."""
    rem = list(t.node_capacity)
    nodes, primary, overflow = [], [], 0.0
    for i, (zi, fi) in enumerate(zip(z, f, strict=True)):
        w = t.resource[i][zi]
        mine = []
        for _ in range(fi):
            k = next((k for k in range(len(rem)) if rem[k] >= w), None)
            if k is None:
                k = max(range(len(rem)), key=lambda j: (rem[j], -j))
                overflow += w - min(w, rem[k])
                rem[k] -= min(w, rem[k])
            else:
                rem[k] -= w
            mine.append(k)
        nodes.append(mine)
        count = [mine.count(k) for k in range(len(rem))]
        primary.append(max(range(len(rem)), key=lambda j: (count[j], -j)))
    return nodes, primary, overflow, rem


def observe(t: Tables, z, f, b, load: float) -> np.ndarray:
    """Eq. 5: per stage the free share of ``w_max``, the load twice (the
    current and the predicted, over 100), the batch latency, the
    throughput over 100, the variant over the last variant's index, the
    replicas over ``f_max``, the batch over ``b_max``, the replicas' cost
    over ``w_max``, then each node's free share of its capacity after the
    placement; stages one after another."""
    n = len(t.alpha)
    used = sum(t.resource[i][z[i]] * f[i] for i in range(n))
    rem = place(t, z, f)[3]
    free = [rem[k] / t.node_capacity[k] for k in range(len(rem))]
    rows = []
    for i in range(n):
        lat = t.alpha[i][z[i]] + t.beta[i][z[i]] * b[i]
        rows += [(t.w_max - used) / t.w_max, load / 100.0, load / 100.0, lat,
                 f[i] * b[i] / lat / 100.0, z[i] / max(1, len(t.alpha[i]) - 1),
                 f[i] / t.f_max, b[i] / t.b_max, f[i] * t.cost[i][z[i]] / t.w_max, *free]
    return np.asarray(rows, np.float64)


def observations(t: Tables, arrivals: np.ndarray, actions: np.ndarray,
                 rate0: float) -> np.ndarray:
    """The observation before each of the ``len(actions)`` decisions and
    the one after the last, which only bootstraps the value: [T + 1,
    state]. Before decision 0 the default configuration (variant 0, one
    replica, batch 1) and ``rate0``; before decision k the configuration
    action k - 1 set and the arrivals of second 10k - 1; after the last,
    the configuration it set and the load of the last decision (as the
    configuration states of the twin)."""
    arrivals = np.asarray(arrivals, np.float64)
    n = len(t.alpha)
    loads = [rate0] + [float(np.count_nonzero((arrivals >= k * t.interval - 1)
                                               & (arrivals < k * t.interval)))
                       for k in range(1, len(actions))]
    configs = [([0] * n, [1] * n, [1] * n)] + [decode(t, a) for a in actions]
    return np.stack([observe(t, *c, load)
                     for c, load in zip(configs, loads + loads[-1:], strict=True)])


def closed_form_latency(t: Tables, z, f, b, demand: float) -> float:
    """The pipeline's latency from the batch-linear model when an interval
    completes nothing: per stage the wait to fill a batch (at most 2 s)
    plus the service time at the slowest node, inflated by 1 / (1 - rho)
    (rho capped at 0.9), plus a hop per pair of stages on different
    primary nodes."""
    nodes, primary, _, _ = place(t, z, f)
    total = 0.0
    for i in range(len(z)):
        lat = t.alpha[i][z[i]] + t.beta[i][z[i]] * b[i]
        speeds = [t.node_speed[k] for k in nodes[i]]
        thr = sum(speeds) * b[i] / lat
        rho = demand / max(thr, 1e-9)
        wait = min(b[i] * f[i] / max(demand, 1e-6), 2.0)
        total += wait + lat / min(speeds) / max(1.0 - rho, 0.1)
    hops = sum(primary[i] != primary[i + 1] for i in range(len(z) - 1))
    return total + t.hop * hops


def replay(t: Tables, arrivals: np.ndarray, actions: np.ndarray, *,
           clock=np.float64) -> dict:
    """Drive the runtime with ``actions`` [T, 3N], one per interval, over
    ``arrivals`` (sorted, seconds), the clock kept in ``clock``. Per
    interval: arrivals, completed requests and the reward."""
    T = clock
    n = len(t.alpha)
    arr = np.asarray(arrivals, np.float64).astype(T)
    head = 0                                    # stage 0 pops from the arrivals
    queues = [deque() for _ in range(n)]        # stage j > 0: (arrival, stamp)
    busy = [dict() for _ in range(n)]           # slot -> (finish, arrivals)
    blocked = [T(0)] * n
    z, f, b = [0] * n, [1] * n, [1] * n
    nodes, primary, _, _ = place(t, z, f)
    wait, hop, cold = T(t.max_wait), T(t.hop), T(t.cold_start)
    now = T(0)
    out = {"completed": [], "reward": [], "arrived": []}

    def stamps(i: int, k: int) -> float:
        """The stamp of stage i's k-th waiting request (inf past the end)."""
        if i == 0:
            return arr[head + k] if head + k < len(arr) else T(np.inf)
        return queues[i][k][1] if k < len(queues[i]) else T(np.inf)

    def due(i: int) -> float:
        if len(busy[i]) >= f[i]:
            return T(np.inf)
        first = stamps(i, 0)
        if first == np.inf:
            return T(np.inf)
        full = max(stamps(i, b[i] - 1), first)
        return max(now, blocked[i], min(full, first + wait))

    for k, action in enumerate(actions):
        t0, t1 = T(k * t.interval), T((k + 1) * t.interval)
        zk, f, b = decode(t, action)
        for i in range(n):
            if zk[i] != z[i]:
                blocked[i] = max(blocked[i], now + cold)
        z = zk
        nodes, primary, overflow, _ = place(t, z, f)
        speeds = [[T(t.node_speed[j]) for j in nodes[i]] for i in range(n)]
        finished: list[float] = []
        while True:
            # the earliest event; at one instant a dispatch before a
            # completion, a lower stage, then a lower slot first
            when, what = T(np.inf), None
            for i in range(n):
                d = due(i)
                if d < when:
                    when, what = d, ("dispatch", i, None)
            for i in range(n):
                for r in sorted(busy[i]):
                    if busy[i][r][0] < when:
                        when, what = busy[i][r][0], ("finish", i, r)
            if what is None or when > t1:
                break
            now = when
            kind, i, r = what
            if kind == "dispatch":
                ready = 0
                while ready < b[i] and stamps(i, ready) <= now:
                    ready += 1
                if i == 0:
                    batch = [float(a) for a in arr[head:head + ready]]
                    head += ready
                else:
                    batch = [queues[i].popleft()[0] for _ in range(ready)]
                free = [s for s in range(f[i]) if s not in busy[i]]
                slot = max(free, key=lambda s: (speeds[i][s], -s))
                service = (T(t.alpha[i][z[i]]) + T(t.beta[i][z[i]]) * T(ready)) / speeds[i][slot]
                busy[i][slot] = (T(now + service), batch)
            else:
                _, batch = busy[i].pop(r)
                if i + 1 == n:
                    finished += [float(now) - a for a in batch]
                else:
                    stamp = T(now + (hop if primary[i] != primary[i + 1] else T(0)))
                    queues[i + 1].extend((a, stamp) for a in batch)
        now = max(now, t1)
        arrived = int(np.count_nonzero((arrivals >= t0) & (arrivals < t1)))
        demand = arrived / t.interval
        thr = len(finished) / t.interval
        lat = (float(np.mean(finished)) if finished
               else closed_form_latency(t, z, f, b, max(demand, 1.0)))
        excess = demand - thr
        w = t.w
        V = sum(t.accuracy[i][z[i]] for i in range(n))
        C = sum(t.cost[i][z[i]] * f[i] for i in range(n))
        qos = w["alpha"] * V + w["beta"] * thr - lat - (
            w["gamma"] * excess if excess >= 0 else w["delta"] * (-excess))
        reward = qos - w["beta_c"] * C - w["gamma_b"] * max(b) - (
            t.penalty if overflow > 0 else 0.0)
        out["completed"].append(len(finished))
        out["reward"].append(reward)
        out["arrived"].append(arrived)
    return {k: np.asarray(v) for k, v in out.items()}
