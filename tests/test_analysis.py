"""Tests for repro.analysis: every reprolint rule (RPL001-RPL006) on seeded
caught/clean fixture pairs, suppression handling, the CLI gate on the repo's
own tree, and the checkify sanitizer (repro.analysis.sanitize) wired around
the jitted twins — a sanitized episode must still match the reference env."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.analysis import RULES, analyze_source, sanitize
from repro.analysis.cli import main as reprolint_main
from repro.api.session import Session
from repro.cluster import PipelineEnv, make_trace
from repro.core import action_to_config, head_sizes, init_policy
from repro.core import runtime_vec as rv
from repro.core import vecenv
from repro.core.mdp import QoSWeights
from repro.serving import make_arrivals

REPO = Path(__file__).resolve().parents[1]
WEIGHTS = QoSWeights()

# a path inside a jit-pure package, so RPL002/RPL005 fixtures are in scope
TWIN = "src/repro/train/fixture.py"


def codes(src, path="fixture.py"):
    return {f.rule for f in analyze_source(src, path)}


class TestRuleCatalogue:
    def test_all_rules_registered(self):
        assert set(RULES) == {"RPL001", "RPL002", "RPL003", "RPL004",
                              "RPL005", "RPL006"}


class TestKeyReuse:
    def test_catches_plain_reuse(self):
        src = (
            "import jax\n"
            "key = jax.random.PRNGKey(0)\n"
            "a = jax.random.normal(key, (2,))\n"
            "b = jax.random.uniform(key)\n"
        )
        found = analyze_source(src, "fixture.py")
        assert [f.rule for f in found] == ["RPL001"]
        assert found[0].line == 4
        assert "'key'" in found[0].message

    def test_catches_loop_carried_reuse(self):
        src = (
            "import jax\n"
            "def draws(key, n):\n"
            "    out = []\n"
            "    for _ in range(n):\n"
            "        out.append(jax.random.normal(key, (2,)))\n"
            "    return out\n"
        )
        assert "RPL001" in codes(src)

    def test_clean_split_chain(self):
        src = (
            "import jax\n"
            "key = jax.random.PRNGKey(0)\n"
            "key, sub = jax.random.split(key)\n"
            "a = jax.random.normal(sub, (2,))\n"
            "key, sub = jax.random.split(key)\n"
            "b = jax.random.uniform(sub)\n"
        )
        assert "RPL001" not in codes(src)

    def test_clean_branch_exclusive_use(self):
        src = (
            "import jax\n"
            "def f(key, flag):\n"
            "    if flag:\n"
            "        return jax.random.normal(key, (2,))\n"
            "    else:\n"
            "        return jax.random.uniform(key)\n"
        )
        assert "RPL001" not in codes(src)


class TestHostNumerics:
    def test_catches_numpy_in_jitted_fn(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return np.sum(x)\n"
        )
        found = [f for f in analyze_source(src, TWIN) if f.rule == "RPL002"]
        assert any("NumPy" in f.message for f in found)

    def test_catches_float_cast_and_branch(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if jnp.sum(x) > 0:\n"
            "        return float(x[0])\n"
            "    return x\n"
        )
        msgs = [f.message for f in analyze_source(src, TWIN)]
        assert any("branch" in m for m in msgs)
        assert any("float()" in m for m in msgs)

    def test_catches_scan_body(self):
        src = (
            "import jax\n"
            "import time\n"
            "def body(carry, x):\n"
            "    return carry, time.perf_counter()\n"
            "def run(xs):\n"
            "    return jax.lax.scan(body, 0.0, xs)\n"
        )
        found = [f for f in analyze_source(src, TWIN) if f.rule == "RPL002"]
        assert any("clock" in f.message for f in found)

    def test_clean_host_side_helper(self):
        # float()/np use outside traced code is fine even in a twin module
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def summarize(x):\n"
            "    return float(x.mean())\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return jnp.where(jnp.sum(x) > 0, x * 2.0, x)\n"
        )
        assert "RPL002" not in codes(src, TWIN)

    def test_out_of_scope_module_not_flagged(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return np.sum(x)\n"
        )
        assert "RPL002" not in codes(src, "src/repro/serving/telemetry.py")


class TestCompatBypass:
    def test_catches_raw_make_mesh(self):
        src = 'import jax\nmesh = jax.make_mesh((2, 2), ("a", "b"))\n'
        found = [f for f in analyze_source(src, "f.py") if f.rule == "RPL003"]
        assert found and "repro.compat.make_mesh" in found[0].message

    def test_catches_make_mesh_import(self):
        src = "from jax import make_mesh\n"
        assert "RPL003" in codes(src)

    def test_catches_raw_get_abstract_mesh(self):
        src = "import jax\nmesh = jax.sharding.get_abstract_mesh()\n"
        found = [f for f in analyze_source(src, "f.py") if f.rule == "RPL003"]
        assert found and "repro.compat.ambient_mesh" in found[0].message

    def test_clean_compat_usage(self):
        src = (
            "import jax\n"
            "from repro.compat import ambient_mesh, make_mesh\n"
            'mesh = make_mesh((2, 2), ("a", "b"))\n'
            "with jax.set_mesh(mesh):\n"
            "    assert ambient_mesh() is not None\n"
        )
        assert "RPL003" not in codes(src)

    def test_compat_module_itself_exempt(self):
        src = "import jax\nf = jax.make_mesh\n"
        assert "RPL003" not in codes(src, "src/repro/compat.py")


class TestSpecSafety:
    def test_catches_unfrozen_untyped_spec(self):
        src = (
            "from dataclasses import dataclass\n"
            "@dataclass\n"
            "class FooSpec:\n"
            "    x: object\n"
        )
        msgs = [f.message for f in analyze_source(src, "f.py")]
        assert any("frozen=True" in m for m in msgs)
        assert any("to_dict" in m for m in msgs)
        assert any("from_dict" in m for m in msgs)
        assert any("not JSON-safe" in m for m in msgs)

    def test_clean_spec(self):
        src = (
            "from dataclasses import dataclass\n"
            "@dataclass(frozen=True)\n"
            "class FooSpec:\n"
            "    name: str\n"
            "    sizes: tuple[int, ...]\n"
            "    child: BarSpec | None\n"
            "    def to_dict(self):\n"
            "        return {}\n"
            "    @classmethod\n"
            "    def from_dict(cls, d):\n"
            "        return cls(**d)\n"
        )
        assert "RPL004" not in codes(src)

    def test_non_spec_class_ignored(self):
        src = "class Helper:\n    x: object\n"
        assert "RPL004" not in codes(src)


class TestCpuLoopLowering:
    def test_catches_dynamic_scatter(self):
        src = (
            "import jax.numpy as jnp\n"
            "def f(x, i, v):\n"
            "    return x.at[i].set(v)\n"
        )
        found = [f for f in analyze_source(src, TWIN) if f.rule == "RPL005"]
        assert found and found[0].severity == "warning"

    def test_catches_sum_cumprod(self):
        src = (
            "import jax.numpy as jnp\n"
            "def f(m):\n"
            "    return jnp.sum(jnp.cumprod(m, axis=-1), axis=-1)\n"
        )
        found = [f for f in analyze_source(src, TWIN) if f.rule == "RPL005"]
        assert found and "argmin" in found[0].message

    def test_clean_static_index_and_argmin(self):
        src = (
            "import jax.numpy as jnp\n"
            "def f(x, m, i, v):\n"
            "    y = x.at[0].set(1.0)\n"
            "    z = x.at[i].add(v)\n"
            "    return y, z, jnp.argmin(m, axis=-1)\n"
        )
        assert "RPL005" not in codes(src, TWIN)

    def test_out_of_scope_module_not_flagged(self):
        src = "def f(x, i, v):\n    return x.at[i].set(v)\n"
        assert "RPL005" not in codes(src, "src/repro/serving/runtime.py")


class TestTimedRegionSync:
    BENCH = "benchmarks/fixture.py"

    def test_catches_sync_in_perf_counter_window(self):
        src = (
            "import time\n"
            "import numpy as np\n"
            "def run(step, x):\n"
            "    t0 = time.perf_counter()\n"
            "    out = step(x)\n"
            "    v = out.item()\n"
            "    host = np.asarray(out)\n"
            "    wall = time.perf_counter() - t0\n"
            "    return wall, v, host\n"
        )
        found = [f for f in analyze_source(src, self.BENCH)
                 if f.rule == "RPL006"]
        assert len(found) == 2 and found[0].severity == "error"

    def test_catches_sync_in_fn_handed_to_timer(self):
        src = (
            "from benchmarks.common import time_fn\n"
            "def run(step, x):\n"
            "    def one_pass():\n"
            "        return step(x).item()\n"
            "    return time_fn(one_pass, reps=3).best\n"
        )
        found = [f for f in analyze_source(src, self.BENCH)
                 if f.rule == "RPL006"]
        assert found and ".item()" in found[0].message

    def test_clean_sync_outside_window(self):
        # syncs after the clock stops (the stop statement reads t0) are fine
        src = (
            "import time\n"
            "import numpy as np\n"
            "def run(step, x):\n"
            "    t0 = time.perf_counter()\n"
            "    out = step(x)\n"
            "    wall = time.perf_counter() - t0\n"
            "    return wall, float(np.asarray(out).mean())\n"
        )
        assert "RPL006" not in codes(src, self.BENCH)

    def test_only_benchmark_paths_in_scope(self):
        src = (
            "import time\n"
            "def run(step, x):\n"
            "    t0 = time.perf_counter()\n"
            "    v = step(x).item()\n"
            "    return time.perf_counter() - t0, v\n"
        )
        assert "RPL006" in codes(src, self.BENCH)
        assert "RPL006" not in codes(src, "src/repro/launch/dryrun.py")

    def test_executor_module_is_jit_pure_scope(self):
        # the measured stage executor joined RPL002's jit-pure set
        src = "import numpy as np\n"
        assert "RPL002" in codes(src, "src/repro/cluster/executor.py")


class TestSuppression:
    BAD = (
        "import jax\n"
        "key = jax.random.PRNGKey(0)\n"
        "a = jax.random.normal(key, (2,))\n"
        "b = jax.random.uniform(key){}\n"
    )

    def test_line_ignore_silences(self):
        src = self.BAD.format("  # reprolint: ignore[RPL001] on purpose")
        assert "RPL001" not in codes(src)

    def test_line_ignore_wrong_code_still_fires(self):
        src = self.BAD.format("  # reprolint: ignore[RPL999]")
        assert "RPL001" in codes(src)

    def test_file_ignore_silences(self):
        src = "# reprolint: ignore-file[RPL001]\n" + self.BAD.format("")
        assert "RPL001" not in codes(src)

    def test_marker_inside_string_does_not_suppress(self):
        src = self.BAD.format(' + str("# reprolint: ignore[RPL001]")')
        assert "RPL001" in codes(src)


class TestCli:
    def test_repo_src_is_clean(self, capsys):
        assert reprolint_main([str(REPO / "src")]) == 0
        out = capsys.readouterr().out
        assert "0 error(s)" in out

    def test_error_finding_exits_nonzero(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text('import jax\nm = jax.make_mesh((2, 2), ("a", "b"))\n')
        assert reprolint_main([str(bad)]) == 1
        assert "RPL003" in capsys.readouterr().out

    def test_warning_exits_zero_unless_strict(self, tmp_path, capsys):
        warn = tmp_path / "train"
        warn.mkdir()
        f = warn / "w.py"
        f.write_text("def f(x, i, v):\n    return x.at[i].set(v)\n")
        assert reprolint_main([str(f)]) == 0
        assert reprolint_main([str(f), "--strict"]) == 1
        capsys.readouterr()

    def test_json_output(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text('import jax\nm = jax.make_mesh((2, 2), ("a", "b"))\n')
        reprolint_main([str(bad), "--json"])
        findings = json.loads(capsys.readouterr().out)
        assert findings[0]["rule"] == "RPL003"
        assert findings[0]["severity"] == "error"
        assert findings[0]["line"] == 2

    def test_select_and_list_rules(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text('import jax\nm = jax.make_mesh((2, 2), ("a", "b"))\n')
        assert reprolint_main([str(bad), "--select", "RPL001"]) == 0
        assert reprolint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for code in RULES:
            assert code in out

    def test_unparseable_file_reported(self, tmp_path, capsys):
        bad = tmp_path / "broken.py"
        bad.write_text("def f(:\n")
        assert reprolint_main([str(bad)]) == 1
        assert "RPL000" in capsys.readouterr().out


class TestCheckifySanitizer:
    def test_checkify_off_by_default(self):
        assert not sanitize.enabled()

    def test_checkify_env_flag(self, monkeypatch):
        monkeypatch.setenv(sanitize.ENV_FLAG, "1")
        assert sanitize.enabled()
        monkeypatch.setenv(sanitize.ENV_FLAG, "0")
        assert not sanitize.enabled()

    def test_checkify_scope_wins_over_env(self, monkeypatch):
        monkeypatch.setenv(sanitize.ENV_FLAG, "1")
        with sanitize.enabled_scope(False):
            assert not sanitize.enabled()
        assert sanitize.enabled()

    def test_checkify_nan_raises(self):
        @sanitize.checked
        def bad(x):
            return jnp.log(x)

        assert np.isnan(float(bad(jnp.float32(-1.0))))  # off: silent NaN
        with sanitize.enabled_scope():
            with pytest.raises(Exception, match="nan"):
                bad(jnp.float32(-1.0))

    def test_checkify_oob_raises(self):
        @sanitize.checked
        def gather(x, i):
            return x[i]

        with sanitize.enabled_scope():
            with pytest.raises(Exception, match="out-of-bounds"):
                gather(jnp.arange(4.0), jnp.int32(9))

    def test_checkify_vecenv_episode_matches_reference(self, monkeypatch):
        """A REPRO_CHECKIFY=1 vecenv episode completes and its rewards match
        the reference PipelineEnv stepping the same action sequence."""
        monkeypatch.setenv(sanitize.ENV_FLAG, "1")
        pipe = api.get_pipeline("serve2").build()
        tables = vecenv.tables_from_pipeline(pipe)
        trace = make_trace("fluctuating", seed=3, seconds=150)
        params = init_policy(jax.random.PRNGKey(0), pipe.n_tasks * 9, head_sizes(pipe))
        traj = vecenv.rollout(
            params,
            tables,
            jnp.asarray(trace, jnp.float32),
            jax.random.PRNGKey(7),
            n_steps=15,
            weights=WEIGHTS,
        )
        env = PipelineEnv(pipe, trace, seed=0)
        for t, action in enumerate(np.asarray(traj["actions"])):
            _, r_ref, _, _ = env.step(action_to_config(pipe, action))
            assert np.isclose(r_ref, float(traj["rewards"][t]), rtol=0.0001, atol=0.05)

    def test_checkify_runtime_replay_matches_reference(self, monkeypatch):
        """A REPRO_CHECKIFY=1 runtime-twin replay completes and matches the
        reference RuntimeEnv on per-interval reward."""
        from repro.cluster import RuntimeEnv

        monkeypatch.setenv(sanitize.ENV_FLAG, "1")
        pipe = api.get_pipeline("serve2").build()
        tables = vecenv.tables_from_pipeline(pipe)
        arrivals = make_arrivals("bursty", rate=20, seed=3)
        rng = np.random.default_rng(0)
        sizes = head_sizes(pipe)
        actions = np.stack(
            [[rng.integers(0, s) for s in sizes] for _ in range(6)]
        ).astype(np.int32)

        env = RuntimeEnv(pipe, arrivals, horizon=60)
        ref_r = []
        for a in actions:
            _, r, _, _ = env.step(action_to_config(pipe, a))
            ref_r.append(float(r))

        ep = rv.episode_arrivals(arrivals, 60)
        out = rv.replay(tables, ep, jnp.asarray(actions), n_steps=6, weights=WEIGHTS)
        assert np.allclose(np.asarray(out["rewards"]), ref_r, atol=0.15)

    def test_checkify_session_toggle(self):
        spec = api.ExperimentSpec(
            pipeline=api.get_pipeline("serve2"),
            scenario=api.get_scenario("steady_low"),
            controller=api.get_controller("random"),
        )
        sess = Session(spec, debug_checkify=True)
        with sess._sanitize_scope():
            assert sanitize.enabled()
        assert not sanitize.enabled()
        off = Session(spec)
        with off._sanitize_scope():
            assert not sanitize.enabled()
