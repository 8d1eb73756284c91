"""Driver for a training cell: the OPD controller retrained with PPO on
the jitted runtime twin (``core/runtime_vec.py``, ``core/ppo.py``).

Set-up (``setup_s``, from process start): the session is built through
the program's public API from the configuration, which the driver first
holds against what the program would run (the pipeline's variant table,
the cluster, the runtime's constants, the PPO defaults, the policy's
sizes, and the mix's arrival streams against ``bench/train_arrivals.py``).
``Session.train`` then runs one episode: it builds the trainer with the
scenario's own arrival streams, compiles the rollout and the update for
the shape the mix sends (the twin pads every arrival array to a multiple
of 512 past 64 spare slots, and the bursty mix's 2683 +- 52 arrivals all
fall in 3072), and is the episode whose steps the reference follows.

The window: ``trainer.train_episode(e, env_seed=e)`` for successive ``e``
until ``--seconds`` have passed; it closes at the end of the episode then
running, so it always holds whole episodes. With ``--trace 1`` the
profiler (host spans, no Python tracer) first records one episode under
the window span, which the per-layer metrics of the device read; the
measured window follows with the profiler off. The twin's event loop
runs some four million device operations an episode on a TPU v5e, and a
trace of two episodes there lost the end of the second.

- ``train_intervals_per_s``: environments x intervals x episodes
  completed, over the window's wall time.

After the window the outputs are checked, as the cell's limits file names
the numbers:

- the update: the reference (``bench/reference/ppo.py``, float32 at the
  highest matmul precision) draws its own initial weights, takes the
  first episode's actions and rewards, computes the observations from
  those actions and the mix's arrivals (``bench/reference/twin.py``), the
  log-probabilities and values with its own policy, and its own
  advantages, and takes the first three minibatch steps; against the
  program's own first three steps: the widest relative gap of a step's
  loss, and by the worst parameter leaf the gap between the two norms of
  the first gradient (the program's read back from Adam's first moment)
  and of the change after the three steps, each over the larger of the
  reference's norm of that leaf and of the median leaf;
- the twin, for the first episode and the window's last: the widest gap
  of an element of an observation the rollout recorded, every
  environment's, against the reference's (over the larger of 1 and the
  reference's element); and for environments drawn from the seed, the plain
  replay of the recorded actions over the same arrivals, its clock in
  float32 as the twin's: intervals whose completed count differs, and the
  widest gap of an interval's reward over the larger of 1 and the
  reference's reward, both over the intervals before the first whose
  count a float64 clock would change (where rounding decides which of two
  events comes first, the two clocks may rightly disagree).

With ``control`` the update's numbers are the control's: the reference in
bfloat16 put in the program's place (``CONTROLS[0]``), so ``correct``
reads false; the int8 control's readings and each fault's go to the
context beside the program's.

``attempted`` counts the window's episodes, ``failed`` the numbers over
their limits. What the trainer's compiled rollout and update were given
and gave back is kept by reference, without changing a value (``Tap``).
"""
from __future__ import annotations

import math
import shutil
import statistics
import tempfile
import time
from pathlib import Path

import jax
import numpy as np
from repro import api
from repro.cluster.env import RuntimeEnv
from repro.core import features, mdp, ppo, runtime_vec
from repro.core.policy import head_sizes
from repro.core.vecenv import tables_from_pipeline

from bench import flops, flops_policy, trace, train_arrivals
from bench.drivers.serve import CompileCounter
from bench.harness import Cell, Result, note
from bench.reference import ppo as ref_ppo
from bench.reference import twin as ref_twin

NUMBERS = ("update_loss_gap", "update_grad_gap", "update_change_gap",
           "twin_completed_mismatch", "twin_reward_gap", "twin_state_gap")
CONTROLS = ("bfloat16", "int8")     # the first is put in the program's place
WARM_EPISODES = 1
TRACED_EPISODES = 1     # episodes a traced run records before its window
STEPS = 3               # minibatch steps the reference follows
TWIN_ENVS = 4           # environments replayed, per episode checked
ROLLOUT = "jit_vec_rollout"
EPISODE_SPAN = "bench.episode"
GRAD_FLOOR = 1e-3       # leaves whose first gradient is under this share of
#                         the median leaf's move by round-off alone


class Tap:
    """Keeps the first and the latest rollout (each with the episode it
    belongs to, which the driver sets) and the first ``STEPS`` minibatch
    updates, as given and returned."""

    def __init__(self):
        self.episode = 0
        self.rollouts: list[tuple[int, dict]] = []
        self.updates: list[tuple[tuple, dict, tuple]] = []
        self._orig = (runtime_vec.vec_rollout, ppo.ppo_minibatch_update)

    def _rollout(self, *a, **k):
        out = self._orig[0](*a, **k)
        self.rollouts[1 if self.rollouts else 0:] = [(self.episode, out)]
        return out

    def _update(self, *a, **k):
        out = self._orig[1](*a, **k)
        if len(self.updates) < STEPS:
            self.updates.append((a, k, out))
        return out

    def __enter__(self):
        runtime_vec.vec_rollout, ppo.ppo_minibatch_update = self._rollout, self._update
        return self

    def __exit__(self, *exc):
        runtime_vec.vec_rollout, ppo.ppo_minibatch_update = self._orig


# ------------------------------------------------------------------ set-up --

def _refuse(what: str, have, want) -> None:
    if have != want:
        raise SystemExit(f"bench: the program runs {what} {have}, the "
                         f"configuration file states {want}")


def check_config(cfg: dict, pipe, env) -> None:
    """Refuse a configuration file that says otherwise than the program."""
    tab = tables_from_pipeline(pipe)
    for key in ("accuracy", "cost", "resource", "alpha", "beta"):
        want = np.asarray([[v[key] for v in s["variants"]] for s in cfg["stages"]],
                          np.float32)
        _refuse(key, np.asarray(getattr(tab, key)).tolist(), want.tolist())
    k, cl, rt = cfg["knobs"], cfg["cluster"], cfg["runtime"]
    _refuse("knobs", [pipe.f_max, pipe.b_max, pipe.batch_choices(), pipe.w_max],
            [k["f_max"], k["b_max"], k["batch_choices"], k["w_max"]])
    _refuse("cluster", ([(n.name, n.capacity, n.speed) for n in pipe.topo.nodes],
                        pipe.topo.hop_latency),
            ([(n["name"], n["capacity"], n["speed"]) for n in cl["nodes"]],
             cl["hop_latency"]))
    _refuse("runtime", [mdp.ADAPTATION_INTERVAL, runtime_vec.COLD_START_SECONDS,
                        runtime_vec.DEFAULT_MAX_WAIT],
            [rt["adaptation_interval_s"], rt["cold_start_s"], rt["max_wait_s"]])
    w = mdp.QoSWeights()
    _refuse("QoS weights", {n: getattr(w, n) for n in cfg["qos_weights"]},
            cfg["qos_weights"])
    c = ppo.PPOConfig(expert_freq=cfg["controller"]["expert_freq"])
    stated = {n: v for n, v in cfg["ppo"].items() if n not in ("optimizer", "note")}
    _refuse("PPO", {n: getattr(c, n) for n in stated}, stated)
    pol = cfg["policy"]
    _refuse("policy", [env.state_dim, features.FEATURE_DIM, features.N_BLOCKS,
                       list(head_sizes(pipe))],
            [pol["state_dim"], pol["feature_dim"], pol["res_blocks"], pol["heads"]])


def check_arrivals(mix: dict, scenario, stream: int) -> None:
    """Refuse a program whose training stream differs from the mix's."""
    have = scenario.train_arrivals(stream)
    _refuse(f"training stream {stream}",
            (have.times(mix["horizon"]).tolist(), float(have.rates(1)[0])),
            (train_arrivals.times(mix, scenario.seed, stream).tolist(),
             float(train_arrivals.rates(mix, 1)[0])))


def build(cell: Cell, seed: int):
    """The session, through the program's public API."""
    cfg, mix = cell.config, cell.traffic
    ctl = cfg["controller"]
    spec = api.ExperimentSpec(
        pipeline=api.get_pipeline(cfg["pipeline"]),
        scenario=api.ScenarioSpec(kind=mix["kind"], rate=mix["rate"], seed=seed % 2**31,
                                  horizon=mix["horizon"]),
        controller=api.ControllerSpec(name=ctl["name"], seed=ctl["seed"],
                                      expert_freq=ctl["expert_freq"],
                                      num_envs=ctl["num_envs"],
                                      train_backend=ctl["train_backend"]),
        backend="runtime")
    sess = api.Session.from_spec(spec)
    scen = spec.scenario
    check_config(cfg, sess.pipe, RuntimeEnv(sess.pipe, scen.train_arrivals(0),
                                            horizon=mix["horizon"]))
    check_arrivals(mix, scen, streams(cfg, WARM_EPISODES)[0])
    return sess


def streams(cfg: dict, episode: int) -> range:
    """The stream numbers of an episode's environments, as the trainer
    numbers them (``OPDTrainer._rollout_vec_runtime``)."""
    envs = cfg["controller"]["num_envs"]
    return range(ppo.VEC_SEED_BASE + episode * envs, ppo.VEC_SEED_BASE + (episode + 1) * envs)


# -------------------------------------------------------------- the checks --

def _paths(tree) -> dict[str, np.ndarray]:
    """A pytree's leaves by path, ``features/blocks/0/fc1/w`` style."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[key] = np.asarray(leaf, np.float32)
    return out


def program_steps(cfg: dict, updates) -> dict:
    """The program's first steps: losses, the first gradient as Adam got it
    (its first moment over 1 - b1), the start and the parameters after the
    last step, by path."""
    b1 = cfg["ppo"]["optimizer"]["b1"]
    first_args, _, first_out = updates[0]
    return {"loss": [float(out[2]) for _, _, out in updates],
            "grad": {k: m / (1 - b1) for k, m in _paths(first_out[1]["m"]).items()},
            "start": _paths(first_args[0]), "end": _paths(updates[-1][2][0])}


def reference_states(cell: Cell, scenario_seed: int, episode: int, actions) -> np.ndarray:
    """Every environment's observations of an episode, from its actions
    and the mix's arrivals: [E, T + 1, state]."""
    cfg, mix = cell.config, cell.traffic
    tables = ref_twin.Tables.from_config(cfg)
    rate0 = float(train_arrivals.rates(mix, 1)[0])
    return np.stack([
        ref_twin.observations(tables, train_arrivals.times(mix, scenario_seed, s), a, rate0)
        for s, a in zip(streams(cfg, episode), np.asarray(actions), strict=True)])


def reference_batches(cfg: dict, params: dict, traj: dict, obs: np.ndarray) -> list[tuple]:
    """The reference's first ``STEPS`` minibatches: its own observations
    ``obs`` [E, T + 1, state], log-probabilities, values and advantages,
    with the rollout's actions and rewards."""
    actions = np.asarray(traj["actions"])
    E, T = actions.shape[:2]
    logp, values, last = ref_ppo.policy_outputs(cfg, params, obs[:, :T], actions, obs[:, T])
    adv, ret = ref_ppo.advantages(cfg, traj["rewards"], values, last)
    s = obs[:, :T].reshape(E * T, -1).astype(np.float32)
    a, lp = actions.reshape(E * T, -1), logp.reshape(E * T)
    return [(s[r], a[r], lp[r], adv[r], ret[r])
            for r in ref_ppo.minibatches(cfg, cfg["controller"]["seed"], E * T, STEPS)]


def reference_steps(cfg: dict, params: dict, batches, precision: str = "highest") -> dict:
    out = ref_ppo.run(cfg, params, batches, precision=precision)
    return {"loss": out["loss"], "grad": out["grad"], "start": out["start"],
            "end": out["params"][-1]}


def readings(got: dict, ref: dict) -> dict[str, float]:
    """The update's three numbers: ``got``'s steps against the reference's.
    Leaves whose reference gradient is under ``GRAD_FLOOR`` of the median
    leaf's are left out of the change."""
    def norms(d):
        return {k: float(np.linalg.norm(v)) for k, v in d.items()}

    def worst(a, b, keys):
        med = statistics.median(b[k] for k in keys)
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in keys)

    g_got, g_ref = norms(got["grad"]), norms(ref["grad"])
    med = statistics.median(g_ref.values())
    moved = [k for k in g_ref if g_ref[k] >= GRAD_FLOOR * med]
    d_got = norms({k: got["end"][k] - got["start"][k] for k in got["end"]})
    d_ref = norms({k: ref["end"][k] - ref["start"][k] for k in ref["end"]})
    return {"update_loss_gap": max(abs(a - b) / abs(b)
                                   for a, b in zip(got["loss"], ref["loss"], strict=True)),
            "update_grad_gap": worst(g_got, g_ref, list(g_ref)),
            "update_change_gap": worst(d_got, d_ref, moved),
            "leaves_left_out": float(len(g_ref) - len(moved))}


def twin_readings(cell: Cell, scenario_seed: int, seed: int, rollouts, obs) -> dict[str, float]:
    """Each rollout kept against the reference: the widest gap of an
    element of a recorded observation (every environment) from ``obs``
    (the reference's, by episode), over the larger of 1 and the
    reference's element; and the plain replay of environments drawn
    from the seed against the twin's completed counts and rewards (each
    reward's gap over the larger of 1 and the reference's reward). The
    replay keeps the clock in float32, as the twin does; the intervals
    compared are those before the first whose count a float64 clock would
    change, where rounding decides which of two events comes first."""
    cfg, mix = cell.config, cell.traffic
    tables = ref_twin.Tables.from_config(cfg)
    rng = np.random.default_rng([seed, 0x7477696E])
    mismatch, gap, state_gap, compared, replayed = 0, 0.0, 0.0, 0, 0
    for episode, traj in rollouts:
        actions = np.asarray(traj["actions"])
        states = np.asarray(traj["states"], np.float64)
        want = obs[episode][:, :states.shape[1]]
        state_gap = max(state_gap, float(np.max(np.abs(states - want)
                                                / np.maximum(1.0, np.abs(want)))))
        completed = np.rint(np.asarray(traj["completed"]))
        rewards = np.asarray(traj["rewards"])
        env_streams = streams(cfg, episode)
        for i in sorted(rng.choice(len(env_streams), TWIN_ENVS, replace=False).tolist()):
            arrivals = train_arrivals.times(mix, scenario_seed, env_streams[i])
            ref = ref_twin.replay(tables, arrivals, actions[i], clock=np.float32)
            wide = ref_twin.replay(tables, arrivals, actions[i])
            keep = np.cumprod(ref["completed"] == wide["completed"]).astype(bool)
            mismatch += int(np.sum((ref["completed"] != completed[i]) & keep))
            rel = np.abs(ref["reward"] - rewards[i]) / np.maximum(1.0, np.abs(ref["reward"]))
            gap = max(gap, float(np.max(rel, where=keep, initial=0.0)))
            compared += int(keep.sum())
            replayed += 1
    return {"twin_completed_mismatch": float(mismatch), "twin_reward_gap": gap,
            "twin_state_gap": state_gap,
            "twin_intervals_compared": float(compared), "twin_replayed": float(replayed)}


def completion_lost(traj: dict) -> dict:
    """Every environment's first interval reports one completion fewer."""
    bad = np.asarray(traj["completed"]).copy()
    bad[:, 0] -= 1
    return {**traj, "completed": bad}


def reward_moved(traj: dict) -> dict:
    """Every environment's first interval reports its reward moved by 2 %
    of its size, and by at least 0.02."""
    bad = np.asarray(traj["rewards"]).copy()
    bad[:, 0] += 0.02 * np.maximum(1.0, np.abs(bad[:, 0]))
    return {**traj, "rewards": bad}


def state_stale(traj: dict) -> dict:
    """Every environment's last decision is recorded beside the
    observation of the one before."""
    bad = np.asarray(traj["states"]).copy()
    bad[:, -1] = bad[:, -2]
    return {**traj, "states": bad}


FAULTS = {"completion_lost": completion_lost, "reward_moved": reward_moved,
          "state_stale": state_stale}


def fault_readings(cell: Cell, scenario_seed: int, seed: int, tap: Tap, ref: dict,
                   obs) -> dict:
    """What each fault a training cell can have reads, planted in what the
    program produced: a step that returns its state unchanged; each of the
    three minibatches cut to its first half, the mean taken over it (the
    program's own compiled update); an answer altered where it is
    produced, in the twin's records of the first episode (``FAULTS``)."""
    got = program_steps(cell.config, tap.updates)
    out = {"unchanged": readings({**got, "end": got["start"]}, ref)}
    update = tap._orig[1]
    params, opt = tap.updates[0][0][:2]
    half = []
    for a, k, _ in tap.updates:
        cut = [x[:x.shape[0] // 2] for x in a[2:7]]
        res = update(params, opt, *cut, *a[7:], **k)
        half.append((a, k, res))
        params, opt = res[:2]
    out["half_batch"] = readings(program_steps(cell.config, half), ref)
    episode, traj = tap.rollouts[0]
    for name, fault in FAULTS.items():
        out[name] = twin_readings(cell, scenario_seed, seed, [(episode, fault(traj))], obs)
    return out


# --------------------------------------------------------------------- run --

def run(cell: Cell, args, *, t0: float) -> Result:
    cfg, mix = cell.config, cell.traffic
    scenario_seed = args.seed % 2**31
    n_steps = mix["horizon"] // mdp.ADAPTATION_INTERVAL
    peak = flops.peaks(jax.devices()[0].device_kind) if args.trace else None
    counter = CompileCounter()
    split: dict[str, float] = {"jax_init": time.perf_counter() - t0}
    t = time.perf_counter()
    sess = build(cell, args.seed)
    split["build"] = time.perf_counter() - t

    tap = Tap()
    with tap:
        t = time.perf_counter()
        tap.episode = WARM_EPISODES
        sess.train(episodes=WARM_EPISODES)
        trainer = sess.trainer
        split["warm_up"] = time.perf_counter() - t

        tracedir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
        if tracedir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(tracedir, profiler_options=options)
        setup_s = time.perf_counter() - t0
        walls: list[float] = []
        e = WARM_EPISODES + 1

        def episode():
            nonlocal e
            tap.episode = e
            with jax.profiler.TraceAnnotation(EPISODE_SPAN):
                trainer.train_episode(e, env_seed=e)
            e += 1
            walls.append(time.perf_counter())

        if tracedir:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                for _ in range(TRACED_EPISODES):
                    episode()
            jax.profiler.stop_trace()
            walls.clear()
        first = e
        t_open = time.perf_counter()
        while not walls or walls[-1] < t_open + args.seconds:
            episode()
        t_close = walls[-1]
    wall = t_close - t_open
    done = e - first
    in_window = counter.between(t_open, t_close)
    per_episode = np.diff([t_open, *walls])
    note("set-up split (s): " + ", ".join(f"{k} {v:.3f}" for k, v in split.items()))
    note(f"setup_s {setup_s:.3f}; compiles or cache reads inside the window: {in_window}")
    note(f"window {wall:.3f} s: {done} episodes of {cfg['controller']['num_envs']} "
         f"environments x {n_steps} intervals; episode s min {per_episode.min():.4f} "
         f"median {np.median(per_episode):.4f} max {per_episode.max():.4f}")
    memory = jax.devices()[0].memory_stats() or {}
    peak_bytes = int(memory.get("peak_bytes_in_use", 0))

    t = time.perf_counter()
    obs = {ep: reference_states(cell, scenario_seed, ep, traj["actions"])
           for ep, traj in tap.rollouts}
    got = program_steps(cfg, tap.updates)
    params = ref_ppo.init_params(cfg, cfg["controller"]["seed"])
    batches = reference_batches(cfg, params, tap.rollouts[0][1], obs[WARM_EPISODES])
    fed = all(np.array_equal(np.asarray(a[3]), b[1])
              for (a, _, _), b in zip(tap.updates, batches, strict=True))
    ref = reference_steps(cfg, params, batches)
    init_gap = max(float(np.max(np.abs(got["start"][k] - ref["start"][k]))) for k in ref["start"])
    note(f"reference: same initial weights (max |diff| {init_gap!r}), same minibatch "
         f"actions {fed}; losses program {got['loss']} reference {ref['loss']}")
    program = readings(got, ref)
    program.update(twin_readings(cell, scenario_seed, args.seed, tap.rollouts, obs))
    numbers = dict(program)
    extra: dict = {}
    if args.control:
        controls = {c: readings(reference_steps(cfg, params, batches, c), ref)
                    for c in CONTROLS}
        numbers.update(controls[CONTROLS[0]])
        extra["controls"] = controls
        extra["faults"] = fault_readings(cell, scenario_seed, args.seed, tap, ref, obs)
    note(f"reference checks took {time.perf_counter() - t:.3f} s")

    limits = cell.limits
    checks = {}
    for name, value in numbers.items():
        if name in limits:
            checks[name] = (float(value), float(limits[name]))
        else:
            note(f"{name} = {value!r} (not compared)")
    failed = sum(not (math.isfinite(v) and v <= lim) for v, lim in checks.values())

    summary = None
    if tracedir:
        summary = trace.reduce(next(iter(sorted(Path(tracedir).rglob("*.xplane.pb")))),
                               program=ROLLOUT, dispatch_prefix=EPISODE_SPAN)
        shutil.rmtree(tracedir, ignore_errors=True)
    device = {"memory_peak_bytes": peak_bytes}
    breakdown = None
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = {"device_ops": summary.top_ops, "idle_gaps": summary.idle_gaps}
        names = [p.name for p in summary.programs]
        note(f"traced episodes {TRACED_EPISODES}: {names.count(ROLLOUT)} rollout and "
             f"{names.count('jit_ppo_minibatch_update')} update programs in the trace")
    return Result(
        metrics={"train_intervals_per_s": done * cfg["controller"]["num_envs"] * n_steps / wall,
                 "setup_s": setup_s},
        checks=checks, attempted=done, failed=failed, device=device, breakdown=breakdown,
        context={"episodes": done, "window_s": wall, "config": cfg, "mix": mix,
                 "traced_episodes": TRACED_EPISODES if summary is not None else 0,
                 "n_steps": n_steps, "peak": peak, "trace": summary,
                 "episode_flops": flops_policy.episode_flops(cfg, n_steps),
                 "setup_split": split, "compiles_in_window": in_window,
                 "readings": program, **extra})
