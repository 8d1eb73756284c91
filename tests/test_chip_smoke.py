"""chip_smoke.py's phases and the published-width switch, on the CPU at
smoke widths: the spec field that selects widths, lazy bf16 weights behind
the runtime, each phase function end to end, and the device phase's
refusal of a CPU backend."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.api.session import build_executors
from repro.cluster.env import RuntimeEnv
from repro.configs import ARCHS
from repro.core.mdp import Config
from repro.serving.arrivals import make_arrivals
from repro.serving.engine import StageServer

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestWidthsSwitch:
    def test_widths_round_trip_through_json(self, chip_smoke):
        exp = chip_smoke.serve_spec(widths="published")
        back = api.ExperimentSpec.from_dict(json.loads(json.dumps(
            exp.to_dict())))
        assert back == exp and back.widths == "published"
        # specs written before the field existed load at smoke widths
        d = exp.to_dict()
        del d["widths"]
        assert api.ExperimentSpec.from_dict(d).widths == "smoke"

    def test_published_widths_build_bf16_servers_without_weights(
            self, chip_smoke):
        servers = build_executors(chip_smoke.serve_spec(widths="published"))
        for server, names in zip(servers, api.get_pipeline("serve2").stages,
                                 strict=True):
            assert [v.name for v in server.variants] == list(names)
            for v in server.variants:
                pub = ARCHS[v.name]
                assert v.dtype == "bfloat16"
                assert (v.n_layers, v.d_model, v.vocab) == (
                    pub.n_layers, pub.d_model, pub.vocab)
            assert server.params == {}        # nothing created up front

    def test_unknown_widths_rejected(self, chip_smoke):
        spec = api.replace(chip_smoke.serve_spec(widths="smoke"),
                           widths="huge")
        with pytest.raises(ValueError, match="widths"):
            build_executors(spec)

    def test_weights_created_bf16_on_first_dispatch(self):
        pipe = api.get_pipeline("serve2").build()
        servers = [
            StageServer(f"stage{i}",
                        [ARCHS[n].smoke().replace(dtype="bfloat16")
                         for n in names], seed=i)
            for i, names in enumerate(api.get_pipeline("serve2").stages)]
        env = RuntimeEnv(pipe, make_arrivals("poisson", rate=2.0, seed=0),
                         horizon=10, executors=servers)
        assert all(s.params == {} for s in servers)
        env.step(Config(z=(1, 0), f=(1, 1), b=(2, 2)))
        env.drain()
        assert sorted(servers[0].params) == [1]
        assert sorted(servers[1].params) == [0]
        for s in servers:
            for leaf in jax.tree.leaves(s.params):
                if jnp.issubdtype(leaf.dtype, jnp.floating):
                    assert leaf.dtype == jnp.bfloat16
            st = s.stats()
            assert st["loaded"] == list(st["executed"])
            assert st["compiled_shapes"] >= 1 and st["compile_s"] > 0.0


class TestPhases:
    def test_twin_and_serve_phases(self, chip_smoke):
        spec = chip_smoke.serve_spec(widths="smoke", horizon=20)
        sess, what = chip_smoke.phase_twin(spec)
        assert "runtime twin" in what
        assert sess.trainer.history["expert"] == [False]
        summary, what = chip_smoke.phase_serve(sess)
        assert summary["served"] == summary["arrived"] > 0
        for st in summary["executors"]:
            assert st["executed"] and st["loaded"] == list(st["executed"])
        assert "served == offered" in what

    def test_stage_step_phase(self, chip_smoke):
        out, what = chip_smoke.phase_stage_step(smoke=True, seq_len=128,
                                                steps=4)
        assert out["rel_err"] <= chip_smoke.LOGIT_RTOL
        assert not out["mosaic"]              # interpreted on the CPU
        assert "starcoder2-3b bfloat16" in what

    def test_sharded_stage_phase(self, chip_smoke):
        out, what = chip_smoke.phase_sharded_stage(smoke=True, cut_layers=1,
                                                   steps=2)
        assert out["model_axis"] == len(jax.devices())
        assert out["rel_err"] <= chip_smoke.LOGIT_RTOL
        assert "granite-3-8b" in what

    def test_device_phase_exits_nonzero_on_cpu(self):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                             capture_output=True, text=True, timeout=300,
                             env=env, cwd=ROOT)
        assert res.returncode != 0
        assert "found platform 'cpu'" in res.stderr
        assert '"ok"' not in res.stdout
