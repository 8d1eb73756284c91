"""CPU tests of the training cell: the two references against the program
they check, the policy's operation count against a hand count, the
driver's window and accounting at a tiny number of environments, and the
comparison that decides ``correct`` against each fault the cell can
have, planted underneath a run whose look for a chip is skipped."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import bench_smoke

REPO = bench_smoke.REPO
sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import flops_policy, harness  # noqa: E402
from bench.reference import ppo as ref_ppo  # noqa: E402
from bench.reference import twin as ref_twin  # noqa: E402

CELL = "opd-serve3h.train"
CFG = json.loads((REPO / "bench" / "configs" / "opd-serve3h.json").read_text())
SEED = 2**31 + 4093


def _tiny(root: Path, envs: int = 4) -> harness.Cell:
    """The benchmark copied under ``root`` with the cell cut to ``envs``
    environments an episode."""
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    path = root / "bench" / "configs" / "opd-serve3h.json"
    cfg = json.loads(path.read_text())
    cfg["controller"]["num_envs"] = envs
    path.write_text(json.dumps(cfg))
    return harness.resolve(CELL, root=root)


def _run(cell: harness.Cell, seconds: float = 1.0):
    args = harness.Args(CELL, SEED, seconds, False)
    return harness.driver(cell).run(cell, args, t0=time.perf_counter())


def test_train_configuration_states_what_the_program_runs():
    from repro import api
    from repro.cluster.env import RuntimeEnv
    from repro.serving.arrivals import make_arrivals
    drv = harness.driver(harness.resolve(CELL))
    pipe = api.get_pipeline(CFG["pipeline"]).build()
    env = RuntimeEnv(pipe, make_arrivals("bursty", seed=1), horizon=120)
    drv.check_config(CFG, pipe, env)
    bad = json.loads(json.dumps(CFG))
    bad["stages"][1]["variants"][0]["alpha"] *= 1.01
    with pytest.raises(SystemExit, match="alpha"):
        drv.check_config(bad, pipe, env)


def test_reference_ppo_follows_the_program_update_at_highest_precision():
    from repro.core import ppo
    from repro.core.policy import init_policy, log_prob_entropy
    pol = CFG["policy"]
    seed = 12345
    program = init_policy(jax.random.PRNGKey(seed), pol["state_dim"], tuple(pol["heads"]))
    mine = ref_ppo.init_params(CFG, seed)
    drv = harness.driver(harness.resolve(CELL))
    for k, leaf in drv._paths(program).items():
        np.testing.assert_array_equal(leaf, np.asarray(mine[k]))
    rng = np.random.default_rng(7)
    mb = CFG["ppo"]["minibatch"]
    batches = []
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            s = rng.normal(size=(mb, pol["state_dim"])).astype(np.float32)
            a = np.stack([rng.integers(0, n, mb) for n in pol["heads"]], 1).astype(np.int32)
            lp = np.asarray(log_prob_entropy(program, jnp.asarray(s), jnp.asarray(a))[0])
            lp = lp + rng.normal(scale=0.05, size=mb).astype(np.float32)
            batches.append((s, a, lp, rng.normal(size=mb).astype(np.float32),
                            rng.normal(size=mb).astype(np.float32)))
        ref = ref_ppo.run(CFG, mine, batches)
        p, opt, losses = program, ppo.adamw_init(program), []
        c = CFG["ppo"]
        for s, a, lp, adv, ret in batches:
            p, opt, loss, *_ = ppo.ppo_minibatch_update(
                p, opt, *map(jnp.asarray, (s, a, lp, adv, ret)),
                jnp.asarray(s), jnp.asarray(a), jnp.float32(0.0),
                clip_eps=c["clip_eps"], c1=c["c1"], c2=c["c2"], lr=c["lr"])
            losses.append(float(loss))
    np.testing.assert_allclose(losses, ref["loss"], rtol=1e-5)
    start = drv._paths(program)
    for k, leaf in drv._paths(p).items():
        # three Adam steps move an element by at most 3 lr = 9e-4; an element
        # whose gradient is near zero may land a few 1e-6 apart
        np.testing.assert_allclose(leaf, ref["params"][-1][k], atol=1e-5)
        np.testing.assert_allclose(np.linalg.norm(leaf - start[k]),
                                   np.linalg.norm(ref["params"][-1][k] - start[k]), rtol=1e-4)


@pytest.mark.parametrize("seed", [0, 3, 2**31 + 11])
def test_reference_twin_matches_the_twin_and_the_runtime_on_bursty_traffic(seed):
    from repro import api
    from repro.cluster.env import RuntimeEnv
    from repro.core import runtime_vec
    from repro.core.mdp import QoSWeights
    from repro.core.policy import action_to_config
    from repro.core.vecenv import tables_from_pipeline
    from repro.serving.arrivals import make_arrivals
    pipe = api.get_pipeline(CFG["pipeline"]).build()
    process = make_arrivals("bursty", rate=25.0, seed=seed)
    rng = np.random.default_rng(seed)
    actions = np.stack([[rng.integers(0, n) for n in CFG["policy"]["heads"]]
                        for _ in range(12)]).astype(np.int32)
    tables = ref_twin.Tables.from_config(CFG)
    narrow = ref_twin.replay(tables, process.times(120), actions, clock=np.float32)
    wide = ref_twin.replay(tables, process.times(120), actions)
    twin = runtime_vec.replay(tables_from_pipeline(pipe),
                              runtime_vec.episode_arrivals(process, 120),
                              jnp.asarray(actions), n_steps=12, weights=QoSWeights(),
                              max_wait=0.25)
    np.testing.assert_array_equal(narrow["completed"], np.asarray(twin["completed"]))
    np.testing.assert_allclose(narrow["reward"], np.asarray(twin["rewards"]),
                               rtol=1e-4, atol=1e-4)
    env = RuntimeEnv(pipe, process, horizon=120)
    steps = [env.step(action_to_config(pipe, a)) for a in actions]
    np.testing.assert_array_equal(wide["completed"], [s[3]["processed"] for s in steps])
    np.testing.assert_allclose(wide["reward"], [s[1] for s in steps], rtol=1e-9, atol=1e-9)
    assert narrow["completed"].sum() > 0


def test_policy_flops_match_a_hand_count_for_one_minibatch():
    # per row: projection 36x128, three blocks of two 128x128 layers, heads
    # 128 x (2+8+6) x 3 = 128x48, value 128x1: 109,184 multiply-adds
    assert flops_policy.forward_flops(CFG, 64) == 2 * 64 * 109_184
    # backward: each weight's gradient and each input's, but the states'
    assert flops_policy.backward_flops(CFG, 64) == 2 * 2 * 64 * 109_184 - 2 * 64 * 36 * 128
    step = 2 * (3 * 2 * 64 * 109_184 - 2 * 64 * 36 * 128)    # policy batch and BC batch
    assert step == 82_673_664
    ep = flops_policy.episode_flops(CFG, 12)
    assert ep["update"] == 4 * 12 * step
    assert ep["rollout"] == 2 * 768 * 109_184 + 2 * 64 * (36 * 128 + 6 * 128 * 128 + 128)
    assert ep["total"] == ep["update"] + ep["rollout"]


@pytest.mark.parametrize("seed", [0, 2**31 - 1])
def test_mix_streams_are_the_programs_training_streams(seed):
    from bench import train_arrivals
    from repro import api
    mix = json.loads((REPO / "bench" / "traffic" / "retrain.json").read_text())
    scen = api.ScenarioSpec(kind=mix["kind"], rate=mix["rate"], seed=seed,
                            horizon=mix["horizon"])
    for stream in (0, 100_064, 100_127):
        have = scen.train_arrivals(stream)
        np.testing.assert_array_equal(train_arrivals.times(mix, seed, stream),
                                      have.times(mix["horizon"]))
        np.testing.assert_array_equal(train_arrivals.rates(mix, mix["horizon"]),
                                      have.rates(mix["horizon"]))
    drv = harness.driver(harness.resolve(CELL))
    drv.check_arrivals(mix, scen, 100_064)
    with pytest.raises(SystemExit, match="training stream"):
        drv.check_arrivals({**mix, "seed_stride": 7907}, scen, 100_064)


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_reference_observations_match_the_twin_and_the_runtime(seed):
    from repro import api
    from repro.cluster.env import RuntimeEnv
    from repro.core import runtime_vec
    from repro.core.mdp import QoSWeights
    from repro.core.policy import action_to_config, apply_policy, init_policy
    from repro.core.vecenv import tables_from_pipeline
    from repro.serving.arrivals import make_arrivals
    pipe = api.get_pipeline(CFG["pipeline"]).build()
    process = make_arrivals("bursty", rate=25.0, seed=seed)
    params = init_policy(jax.random.PRNGKey(seed % 97), CFG["policy"]["state_dim"],
                         tuple(CFG["policy"]["heads"]))
    traj = runtime_vec.rollout(params, tables_from_pipeline(pipe),
                               runtime_vec.episode_arrivals(process, 120),
                               jax.random.PRNGKey(seed % 89), n_steps=12,
                               weights=QoSWeights())
    actions = np.asarray(traj["actions"])
    obs = ref_twin.observations(ref_twin.Tables.from_config(CFG), process.times(120),
                                actions, float(process.rates(1)[0]))
    np.testing.assert_allclose(np.asarray(traj["states"]), obs[:12], rtol=1e-6, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        last_value = apply_policy(params, jnp.asarray(obs[12:], jnp.float32))[1]
    np.testing.assert_allclose(np.asarray(traj["last_value"]), last_value, rtol=1e-5, atol=1e-6)
    env = RuntimeEnv(pipe, process, horizon=120)
    seen = np.stack([env.reset()] + [env.step(action_to_config(pipe, a))[0] for a in actions])
    np.testing.assert_allclose(seen[:12], obs[:12], rtol=1e-6, atol=1e-6)
    # the runtime's last observation reads the horizon's last second; the
    # twin's, the configuration's ``bootstrap_load``: only the load differs
    row = obs.shape[1] // len(CFG["stages"])
    load = [row * i + j for i in range(len(CFG["stages"])) for j in (1, 2)]
    rest = np.setdiff1d(np.arange(obs.shape[1]), load)
    np.testing.assert_allclose(seen[12, rest], obs[12, rest], rtol=1e-6, atol=1e-6)
    assert len({tuple(a) for a in actions}) > 1


def test_train_driver_window_holds_whole_episodes_and_counts_them(tmp_path):
    cell = _tiny(tmp_path)
    result = _run(cell)
    line = harness.result_line(cell, result, False)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_intervals_per_s", "setup_s"}
    assert set(line["checks"]) == set(harness.driver(cell).NUMBERS)
    ctx = result.context
    assert ctx["compiles_in_window"] == 0
    assert result.attempted == ctx["episodes"] >= 1
    assert ctx["window_s"] >= 1.0
    intervals = result.metrics["train_intervals_per_s"] * ctx["window_s"]
    assert intervals == pytest.approx(ctx["episodes"] * 4 * 12)
    assert ctx["readings"]["twin_intervals_compared"] > 0
    json.dumps(line)


def test_the_bfloat16_control_in_the_programs_place_fails_a_limit(tmp_path):
    cell = _tiny(tmp_path)
    drv = harness.driver(cell)
    args = harness.Args(CELL, SEED + 1, 1.0, False, True)
    result = drv.run(cell, args, t0=time.perf_counter())
    assert result.correct is False and result.failed >= 1
    control = result.context["controls"][drv.CONTROLS[0]]
    for name, (value, limit) in result.checks.items():
        assert value == control.get(name, result.context["readings"][name])
    program = result.context["readings"]
    assert all(program[k] <= lim for k, lim in cell.limits.items())
    assert set(result.context["controls"]) == set(drv.CONTROLS)
    assert set(result.context["faults"]) >= {"unchanged", "half_batch", *drv.FAULTS}


def _broken_update(kind):
    from repro.core import ppo
    real = ppo.ppo_minibatch_update

    def update(params, opt, *batch, **kw):
        if kind == "unchanged":
            out = real(params, opt, *batch, **kw)
            return (params, opt, *out[2:])
        cut = [x[:x.shape[0] // 2] for x in batch[:5]]
        return real(params, opt, *cut, *batch[5:], **kw)
    return update


def _broken_rollout(fault):
    from repro.core import runtime_vec
    real = runtime_vec.vec_rollout
    return lambda *a, **k: fault(real(*a, **k))


@pytest.mark.parametrize("fault,number", [
    ("unchanged", "update_change_gap"),
    ("half_batch", "update_grad_gap"),
    ("completion_lost", "twin_completed_mismatch"),
    ("reward_moved", "twin_reward_gap"),
    ("state_stale", "twin_state_gap"),
])
def test_each_fault_underneath_a_run_makes_it_incorrect(tmp_path, monkeypatch, fault, number):
    from repro.core import ppo, runtime_vec
    cell = _tiny(tmp_path)
    drv = harness.driver(cell)
    if fault in drv.FAULTS:
        monkeypatch.setattr(runtime_vec, "vec_rollout", _broken_rollout(drv.FAULTS[fault]))
    else:
        monkeypatch.setattr(ppo, "ppo_minibatch_update", _broken_update(fault))
    result = _run(cell)
    assert result.correct is False
    value, limit = result.checks[number]
    assert value > limit


def _record_tool():
    import importlib.util
    path = REPO / "bench" / "tools" / "record_train_trace.py"
    spec = importlib.util.spec_from_file_location("record_train_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_trace_reading_on_a_recorded_tpu_trace():
    from bench import trace
    tool = _record_tool()
    fixture = Path(__file__).resolve().parent / "data" / "tpu_train.xplane.pb"
    drv = harness.driver(harness.resolve(CELL))
    summary = trace.reduce(fixture, program=drv.ROLLOUT, dispatch_prefix=drv.EPISODE_SPAN)
    names = [p.name for p in summary.programs]
    assert names.count("jit_vec_rollout") == tool.EPISODES
    assert names.count("jit_ppo_minibatch_update") == tool.EPISODES * tool.UPDATES
    result = harness.Result(metrics={}, checks={}, attempted=tool.EPISODES, failed=0,
                            device={},
                            context={"trace": summary, "traced_episodes": tool.EPISODES})
    for metric, program in (("twin_rollout_ms", "jit_vec_rollout"),
                            ("ppo_update_ms", "jit_ppo_minibatch_update")):
        device = sum(p.seconds for p in summary.programs if p.name == program)
        value = harness.reader(metric)(result)
        assert value == pytest.approx(1e3 * device / tool.EPISODES) and value > 0
    idle = harness.reader("device_idle_pct.train")(result)
    slept = tool.EPISODES * (tool.BEFORE_ROLLOUT_S + tool.UPDATES * tool.AFTER_UPDATE_S)
    assert 100 * slept / summary.window_s < idle < 100
    assert idle == pytest.approx(100 * (1 - summary.busy_s / summary.window_s))
    assert harness.reader("train_mfu_pct")(result) is None      # no peak: no share
