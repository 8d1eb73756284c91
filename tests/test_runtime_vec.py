"""Tests for the jitted discrete-event runtime twin (repro.core.runtime_vec):
replay equivalence with the reference ``RuntimeEnv``/``ServingRuntime`` loop
across all registered pipelines (including the placement-aware
``serve3-hetero`` on the ``edge-hetero-3`` cluster), arrival precomputation,
closed-loop vec_rollout invariants, the OPDTrainer vec-runtime branch, and
``train_backend="runtime"`` reproducibility through the Session facade,
and the event loop's compiled shape on a described TPU v5e."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import api
from repro.cluster import RuntimeEnv
from repro.core import OPDTrainer, PPOConfig, action_to_config, head_sizes, init_policy
from repro.core import runtime_vec as rv
from repro.core import vecenv
from repro.core.mdp import QoSWeights
from repro.serving import make_arrivals

WEIGHTS = QoSWeights()
GOLDEN = os.path.join(os.path.dirname(__file__), "data", "runtime_vec_serve3h.npz")
HORIZON = 60
N_STEPS = HORIZON // 10


def _random_actions(pipe, rng, n):
    sizes = head_sizes(pipe)
    return np.stack([[rng.integers(0, s) for s in sizes] for _ in range(n)]).astype(
        np.int32
    )


def _reference_episode(pipe, arrivals, actions):
    """Step the real event-driven RuntimeEnv through one action sequence."""
    env = RuntimeEnv(pipe, arrivals, horizon=HORIZON)
    rewards, completed = [], []
    for a in actions:
        _, r, _, info = env.step(action_to_config(pipe, a))
        rewards.append(float(r))
        completed.append(int(info["processed"]))
    return np.asarray(rewards), np.asarray(completed)


class TestTwinEquivalence:
    """The acceptance pin: same arrivals + same config decisions ->
    matching served counts and episode rewards, per registered pipeline."""

    @pytest.mark.parametrize("name", api.list_pipelines())
    def test_replay_matches_runtime_env(self, name):
        pipe = api.get_pipeline(name).build()
        tables = vecenv.tables_from_pipeline(pipe)
        arrivals = make_arrivals("bursty", rate=20, seed=3)
        actions = _random_actions(pipe, np.random.default_rng(0), N_STEPS)

        ref_r, ref_c = _reference_episode(pipe, arrivals, actions)
        ep = rv.episode_arrivals(arrivals, HORIZON)
        out = rv.replay(
            tables,
            ep,
            jnp.asarray(actions),
            n_steps=N_STEPS,
            weights=WEIGHTS,
        )
        twin_c = np.asarray(out["completed"], np.int64)
        twin_r = np.asarray(out["rewards"])

        # event ordering and batch formation are replicated exactly; the
        # float32 clock may move a completion across an interval boundary
        assert np.abs(twin_c - ref_c).max() <= 2, (twin_c, ref_c)
        assert twin_c.sum() == pytest.approx(ref_c.sum(), abs=2)
        assert np.allclose(twin_r, ref_r, atol=0.15), (twin_r, ref_r)

    def test_hetero_placement_interval_rewards(self):
        """serve3-hetero pins the full placement-aware path: node speeds,
        hop latency, cold starts — reward trace matches tightly."""
        pipe = api.get_pipeline("serve3-hetero").build()
        tables = vecenv.tables_from_pipeline(pipe)
        arrivals = make_arrivals("bursty", rate=25, seed=7)
        actions = _random_actions(pipe, np.random.default_rng(5), N_STEPS)
        ref_r, ref_c = _reference_episode(pipe, arrivals, actions)
        ep = rv.episode_arrivals(arrivals, HORIZON)
        out = rv.replay(
            tables,
            ep,
            jnp.asarray(actions),
            n_steps=N_STEPS,
            weights=WEIGHTS,
        )
        assert np.allclose(np.asarray(out["rewards"]), ref_r, atol=0.15)
        assert int(np.asarray(out["completed"]).sum()) > 0


class TestEpisodeArrivals:
    def test_times_match_process_and_pad_inf(self):
        arr = make_arrivals("poisson", rate=12, seed=1)
        ep = rv.episode_arrivals(arr, HORIZON)
        t = np.asarray(arr.times(HORIZON))
        got = np.asarray(ep.times)
        assert np.allclose(got[:len(t)], t.astype(np.float32))
        assert np.all(np.isinf(got[len(t):]))
        # the dispatch window dynamic_slice needs a guaranteed inf tail
        assert got.shape[0] - len(t) >= rv._ARRIVAL_PAD
        assert got.shape[0] % rv._ARRIVAL_BUCKET == 0

    def test_interval_counts_cover_all_arrivals(self):
        arr = make_arrivals("bursty", rate=20, seed=2)
        ep = rv.episode_arrivals(arr, HORIZON)
        t = np.asarray(arr.times(HORIZON))
        assert ep.arrived.shape == (N_STEPS,)
        assert float(jnp.sum(ep.arrived)) == np.count_nonzero(t < HORIZON)

    def test_n_cap_too_small_raises(self):
        arr = make_arrivals("bursty", rate=30, seed=0)
        with pytest.raises(ValueError):
            rv.episode_arrivals(arr, HORIZON, n_cap=rv._ARRIVAL_PAD)

    def test_stack_pads_to_widest(self):
        eps = [
            rv.episode_arrivals(make_arrivals("poisson", rate=r, seed=r), HORIZON)
            for r in (5, 40)
        ]
        batch = rv.stack_episodes(eps)
        assert batch.times.shape[0] == 2
        assert batch.times.shape[1] == max(e.times.shape[0] for e in eps)
        assert np.all(np.isinf(np.asarray(batch.times[0])[eps[0].times.shape[0]:]))


class TestVecRollout:
    B = 4

    def _setup(self, name="serve2"):
        pipe = api.get_pipeline(name).build()
        tables = vecenv.tables_from_pipeline(pipe)
        env = RuntimeEnv(
            pipe,
            make_arrivals("bursty", rate=20, seed=0),
            horizon=HORIZON,
        )
        params = init_policy(jax.random.PRNGKey(0), env.state_dim, head_sizes(pipe))
        eps = rv.stack_episodes(
            [
                rv.episode_arrivals(make_arrivals("bursty", rate=20, seed=i), HORIZON)
                for i in range(self.B)
            ]
        )
        keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(9), s))(
            jnp.arange(self.B)
        )
        return pipe, tables, params, eps, keys

    def test_shapes_and_finiteness(self):
        pipe, tables, params, eps, keys = self._setup()
        out = rv.vec_rollout(
            params,
            tables,
            eps,
            keys,
            n_steps=N_STEPS,
            weights=WEIGHTS,
        )
        assert out["actions"].shape == (self.B, N_STEPS, len(head_sizes(pipe)))
        assert out["last_value"].shape == (self.B,)
        for k in ("rewards", "values", "logps", "qos", "completed"):
            assert out[k].shape == (self.B, N_STEPS)
            assert np.isfinite(np.asarray(out[k])).all(), k

    @given(st.integers(0, 10_000))
    @settings(max_examples=6, deadline=None)
    def test_permutation_invariant_along_env_axis(self, perm_seed):
        """Each env consumes only its own (arrivals, key): permuting the
        env axis of the inputs permutes every output exactly."""
        _, tables, params, eps, keys = self._setup()
        out = rv.vec_rollout(
            params,
            tables,
            eps,
            keys,
            n_steps=N_STEPS,
            weights=WEIGHTS,
        )
        perm = np.random.default_rng(perm_seed).permutation(self.B)
        eps_p = jax.tree.map(lambda x: x[perm], eps)
        out_p = rv.vec_rollout(
            params,
            tables,
            eps_p,
            keys[perm],
            n_steps=N_STEPS,
            weights=WEIGHTS,
        )
        for k in out:
            assert np.array_equal(np.asarray(out[k])[perm], np.asarray(out_p[k])), k

    def test_rollout_actions_replay_to_same_rewards(self):
        """A vec_rollout trajectory is a real runtime episode: feeding its
        action sequence back through the reference RuntimeEnv yields the
        same rewards."""
        pipe, tables, params, eps, keys = self._setup()
        out = rv.vec_rollout(
            params,
            tables,
            eps,
            keys,
            n_steps=N_STEPS,
            weights=WEIGHTS,
        )
        i = 0
        ref_r, _ = _reference_episode(
            pipe,
            make_arrivals("bursty", rate=20, seed=i),
            np.asarray(out["actions"][i]),
        )
        assert np.allclose(np.asarray(out["rewards"][i]), ref_r, atol=0.15)


def _golden_case():
    """serve3-hetero, 4 envs of bursty 25 req/s, 12 intervals: the case
    ``tests/data/runtime_vec_serve3h.npz`` holds the outputs of (written with
    ``np.savez(path, **outputs)`` by the twin's earlier ``[S, Q, 2]`` queue
    layout, before the buffer became one row per stage and column)."""
    pipe = api.get_pipeline("serve3-hetero").build()
    tables = vecenv.tables_from_pipeline(pipe)
    env = RuntimeEnv(pipe, make_arrivals("bursty", rate=25, seed=0), horizon=120)
    params = init_policy(jax.random.PRNGKey(0), env.state_dim, head_sizes(pipe))
    eps = rv.stack_episodes(
        [
            rv.episode_arrivals(make_arrivals("bursty", rate=25, seed=i), 120)
            for i in range(4)
        ]
    )
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(9), s))(
        jnp.arange(4)
    )
    return rv.vec_rollout(params, tables, eps, keys, n_steps=12, weights=WEIGHTS)


def test_vec_rollout_reproduces_recorded_episode_bit_for_bit():
    """Where the queue's bytes live changes no clock, event order or float:
    every output equals the recorded one bit for bit."""
    want = np.load(GOLDEN)
    got = _golden_case()
    assert sorted(want.files) == sorted(got)
    for k in want.files:
        g = np.asarray(got[k])
        assert g.dtype == want[k].dtype and g.shape == want[k].shape, k
        assert g.tobytes() == want[k].tobytes(), k


class TestTrainerVecRuntime:
    def _factory(self, pipe):
        def arrivals(seed):
            return make_arrivals("bursty", rate=20, seed=seed)

        def make_env(seed):
            return RuntimeEnv(pipe, arrivals(seed), horizon=HORIZON)
        return make_env, arrivals

    def test_vec_runtime_branch_updates_params(self):
        pipe = api.get_pipeline("serve2").build()
        make_env, arrivals = self._factory(pipe)
        tr = OPDTrainer(
            pipe,
            make_env,
            ppo=PPOConfig(epochs=1, expert_freq=2),
            seed=0,
            num_envs=4,
            vec_runtime=arrivals,
        )
        assert tr._vec_runtime is not None
        before = jax.tree.map(jnp.copy, tr.params)
        tr.train_episode(1)                     # 1 % 2 != 0 -> runtime twin
        assert tr.history["expert"] == [False]
        delta = jax.tree.reduce(
            lambda a,
            b: a + b,
            jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()), before, tr.params),
        )
        assert delta > 0
        assert np.isfinite(tr.history["loss"]).all()

    def test_expert_episode_steps_real_runtime(self):
        pipe = api.get_pipeline("serve2").build()
        make_env, arrivals = self._factory(pipe)
        tr = OPDTrainer(
            pipe,
            make_env,
            ppo=PPOConfig(epochs=1, expert_freq=1),
            seed=0,
            num_envs=4,
            vec_runtime=arrivals,
        )
        tr.train_episode(1)                     # expert -> legacy RuntimeEnv
        assert tr.history["expert"] == [True]
        assert len(tr.expert_states) > 0


class TestClosedLoopAcceptance:
    def test_vec_trained_matches_legacy_trained_on_hetero_cluster(self):
        """Acceptance (ISSUE 6): an OPD policy trained through the
        vectorized runtime twin matches or beats one trained with the
        legacy per-step RuntimeEnv loop, evaluated closed-loop on
        serve3-hetero (the edge-hetero-3 cluster), at equal tiny budgets."""
        from repro.core import OPDPolicy, run_episode
        pipe = api.get_pipeline("serve3-hetero").build()

        def arrivals(seed):
            return make_arrivals("bursty", rate=20, seed=seed)

        def make_env(seed):
            return RuntimeEnv(pipe, arrivals(seed), horizon=HORIZON)

        def train(vec):
            tr = OPDTrainer(
                pipe,
                make_env,
                ppo=PPOConfig(epochs=2, expert_freq=2),
                seed=0,
                num_envs=4 if vec else 1,
                vec_runtime=arrivals if vec else None,
            )
            tr.train(4)
            return tr.params

        def evaluate(params):
            rs = []
            for seed in (500, 501):
                env = RuntimeEnv(pipe, arrivals(seed), horizon=HORIZON)
                out = run_episode(env, OPDPolicy(pipe, params, greedy=True))
                rs.append(float(np.mean(out["reward"])))
            return float(np.mean(rs))

        legacy = evaluate(train(vec=False))
        vec = evaluate(train(vec=True))
        # equal-budget parity: identical expert episodes dominate learning
        # at this scale, so the twin-trained policy must land in the same
        # closed-loop reward band as the reference-trained one
        assert vec >= legacy - max(2.0, 0.5 * abs(legacy)), (vec, legacy)


class TestSessionRuntimeBackend:
    def _spec(self):
        return api.ExperimentSpec(
            pipeline=api.get_pipeline("serve2"),
            scenario=api.replace(
                api.get_scenario("bursty"),
                rate=20.0,
                seed=4,
                horizon=HORIZON,
            ),
            controller=api.replace(
                api.get_controller("opd"),
                train_episodes=2,
                num_envs=2,
                train_backend="runtime",
            ),
            backend="runtime",
        )

    def test_train_backend_roundtrips_through_json(self):
        spec = self._spec()
        back = api.ExperimentSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
        assert back == spec
        assert back.controller.train_backend == "runtime"

    def test_unknown_train_backend_rejected(self):
        spec = api.replace(
            self._spec(),
            controller=api.replace(self._spec().controller, train_backend="quantum"),
        )
        with pytest.raises(ValueError, match="train_backend"):
            api.Session.from_spec(spec.to_dict()).train()

    def test_train_reproducible_from_serialized_spec(self):
        """Session.train with train_backend="runtime" is reproducible from
        a serialized ExperimentSpec — every arrival stream and policy draw
        derives from spec seeds."""
        blob = json.dumps(self._spec().to_dict())

        def params_of():
            sess = api.Session.from_spec(blob)
            sess.train()
            return sess.trainer.params, list(sess.trainer.history["reward"])

        p1, h1 = params_of()
        p2, h2 = params_of()
        assert h1 == h2
        same = jax.tree.map(lambda a, b: bool((a == b).all()), p1, p2)
        assert all(jax.tree.leaves(same))


def _hlo_computations(text):
    """{computation name: (header line, body lines)} of an HLO module."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = (line, [])
        elif name is not None and line != "}":
            comps[name][1].append(line)
    return comps


def _reachable(comps, root):
    """``root`` and every computation it calls, transitively."""
    seen, todo = {root}, [root]
    while todo:
        for line in comps[todo.pop()][1]:
            for m in re.finditer(
                r"(?:calls|body|condition|to_apply|branch_computations)=\{?%([\w.\-]+)",
                line,
            ):
                if m.group(1) in comps and m.group(1) not in seen:
                    seen.add(m.group(1))
                    todo.append(m.group(1))
    return seen


def _elements(shape):
    """Element count of an HLO array type's dimensions, "64,6,3104"."""
    return int(np.prod([int(d) for d in shape.split(",") if d]))


def test_event_loop_relays_out_no_queue_buffer_on_v5e(one_chip):
    """At the training cell's shapes (64 envs of 3072 arrival slots, b_max
    32, three stages, 12 intervals), the event loop's compiled body holds no
    copy of the whole queue buffer and no per-environment loop over it.

    On a TPU a minor axis of 2 is padded to 128 lanes: with the buffer as
    [S, Q, 2] each batched read wanted another tiling than the loop carried,
    and XLA relaid out the whole buffer twice per event."""
    E, n_cap, n_steps = 64, 3072, 12
    pipe = api.get_pipeline("serve3-hetero").build()
    tables = vecenv.tables_from_pipeline(pipe)
    env = RuntimeEnv(pipe, make_arrivals("bursty", rate=25, seed=0), horizon=120)
    params = jax.eval_shape(
        lambda k: init_policy(k, env.state_dim, head_sizes(pipe)),
        jax.random.PRNGKey(0),
    )
    eps = rv.EpisodeArrivals(
        times=jax.ShapeDtypeStruct((E, n_cap), jnp.float32),
        arrived=jax.ShapeDtypeStruct((E, n_steps), jnp.float32),
        load_obs=jax.ShapeDtypeStruct((E, n_steps), jnp.float32),
    )
    keys = jax.ShapeDtypeStruct((E, 2), jnp.uint32)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree
        )

    text = (
        rv.vec_rollout.__wrapped__.lower(
            on_chip(params),
            on_chip(tables),
            on_chip(eps),
            on_chip(keys),
            n_steps=n_steps,
            weights=WEIGHTS,
        )
        .compile()
        .as_text()
    )
    S, B = tables.n_tasks, tables.batch_slots.shape[0]
    n_queue = E * S * (n_cap + B) * 2
    comps = _hlo_computations(text)

    def carries_queue(header):
        return any(
            _elements(d) == n_queue for d in re.findall(r"f32\[([\d,]*)\]", header)
        )

    # bodies of the whiles whose carry holds the queue buffer, nested: the
    # interval scan, then the event loop inside its body
    loops = set()
    for _, lines in comps.values():
        for line in lines:
            m = re.search(r" while\(.*body=%([\w.\-]+)", line)
            if m and carries_queue(comps[m.group(1)][0]):
                loops.add(m.group(1))
    depth = {
        body: sum(body in _reachable(comps, other) for other in loops) - 1
        for body in loops
    }
    event_loops = [b for b, d in depth.items() if d == 1]
    assert len(event_loops) == 1, depth
    inside = _reachable(comps, event_loops[0])
    copies = [
        line.strip()[:160]
        for c in inside
        for line in comps[c][1]
        for d in re.findall(r"= f32\[([\d,]*)\]\S* copy\(", line)
        if _elements(d) == n_queue
    ]
    assert not copies, copies
    per_env = sorted(loops & inside - {event_loops[0]})
    assert not per_env, per_env
