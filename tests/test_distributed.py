"""Distribution correctness: sharding specs are well-formed for every arch,
and the shard_map expert-parallel MoE path is numerically identical to the
local path. Multi-device cases run in a SUBPROCESS with forced host devices
so this pytest session keeps seeing exactly 1 device (the dry-run owns the
512-device configuration)."""
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import ARCHS
from repro.distributed import sharding as shd


class TestShardingSpecs:
    @pytest.mark.parametrize("name", sorted(ARCHS))
    def test_param_specs_divide_evenly(self, name):
        """Every param leaf's spec must divide its dims on the 16x16 mesh —
        checked abstractly (no devices needed)."""
        cfg = ARCHS[name]
        mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
        for kind in ("train", "decode"):
            psh = shd.param_shardings(cfg, mesh, kind=kind)
            shapes = jax.eval_shape(
                lambda k: __import__("repro.models.api", fromlist=["api"]).init_model(
                    k,
                    cfg,
                ),
                jax.random.PRNGKey(0),
            )
            for leaf, sh in zip(jax.tree.leaves(shapes), jax.tree.leaves(psh),
                                strict=True):
                for dim, ax in zip(leaf.shape, tuple(sh.spec) + (None,) * 9,
                                   strict=False):
                    if ax is None:
                        continue
                    axes = (ax,) if isinstance(ax, str) else tuple(ax)
                    n = 1
                    for a in axes:
                        n *= mesh.shape[a]
                    assert dim % n == 0, (name, kind, leaf.shape, sh.spec)

    def test_zero1_adds_data_axis_somewhere(self):
        cfg = ARCHS["llama3.2-1b"]
        mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
        osh = shd.opt_shardings(cfg, mesh)
        specs = [s.spec for s in jax.tree.leaves(osh)]
        assert any(
            ("data" in str(sp) for sp in specs)
        ), "ZeRO-1 should shard at least one moment leaf over data"


MOE_EP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro import nn
    from repro.compat import make_mesh

    key = jax.random.PRNGKey(0)
    p = nn.init_moe(key, 32, 64, 16)          # E=16 -> padded stays 16
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

    y_local, aux_local = nn.moe(p, x, top_k=2)            # no mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        y_ep, aux_ep = jax.jit(lambda p_, x_: nn.moe(p_, x_, top_k=2))(p, x)

    err = float(jnp.abs(y_local - y_ep).max())
    assert err < 1e-4, f"EP vs local mismatch: {err}"
    lb = abs(float(aux_local["lb_loss"]) - float(aux_ep["lb_loss"]))
    assert lb < 1e-4, f"lb_loss mismatch {lb}"
    print("EP==local OK", err)
""")

DRYRUN_SMOKE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import make_mesh
    from repro.configs import ARCHS
    from repro.distributed import sharding as shd
    from repro.models import api, steps
    from repro.models.config import InputShape
    from repro.train import adamw_init

    # a reduced arch on a tiny 2x4 mesh exercises the full dry-run plumbing
    cfg = ARCHS["granite-moe-3b-a800m"].smoke().replace(
        n_experts=16, top_k=2, n_heads=4, n_kv=4)
    shape = InputShape("t", 64, 8, "train")
    mesh = make_mesh((2, 4), ("data", "model"))
    bs = steps.batch_specs(cfg, shape)
    bsh = shd.batch_shardings(cfg, shape, mesh)
    psh = shd.param_shardings(cfg, mesh)
    zsh = shd.opt_shardings(cfg, mesh)
    params_shape = jax.eval_shape(lambda k: api.init_model(k, cfg),
                                  jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(adamw_init, params_shape)
    osh = {"m": zsh, "v": zsh, "step": NamedSharding(mesh, P())}
    step = steps.make_train_step(cfg)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=(psh, osh, bsh),
                           donate_argnums=(0, 1)).lower(
            params_shape, opt_shape, bs).compile()
    print("compiled OK", compiled.cost_analysis().get("flops", 0) > 0)
""")


def _run_sub(script: str):
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=420,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert res.returncode == 0, f"stdout:{res.stdout}\nstderr:{res.stderr[-2000:]}"
    return res.stdout


class TestMultiDevice:
    def test_moe_expert_parallel_matches_local(self):
        out = _run_sub(MOE_EP_SCRIPT)
        assert "EP==local OK" in out

    def test_dryrun_plumbing_compiles_on_8_devices(self):
        out = _run_sub(DRYRUN_SMOKE_SCRIPT)
        assert "compiled OK True" in out
