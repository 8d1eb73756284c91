"""Compiles for a described TPU v5e: the Pallas kernels of the main path at
starcoder2-3b's head shapes, and one published-width starcoder2-3b decode
step. Nothing runs — the TPU compiler, installed here, compiles for a chip
that is described and not attached, and refuses what the chip would
(block tiling, VMEM, memory). The topology is described inside a
module-scoped fixture, never while a module is imported, so every pytest
worker collects the same tests and only the one given this file loads the
TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.models import api, steps
from repro.models.config import InputShape

SC = ARCHS["starcoder2-3b"]
H, HKV, D = SC.n_heads, SC.n_kv, SC.head_dim          # 24, 2, 128
CACHE = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # executables compiled for a described chip are written to the
        # persistent cache but cannot be read back without one: keep the
        # cache off so these compiles stay silent
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_compiles_for_v5e(one_chip):
    q = _spec((1, CACHE, H, D), jnp.bfloat16, one_chip)
    kv = _spec((1, CACHE, HKV, D), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_for_v5e(one_chip):
    q = _spec((8, 1, H, D), jnp.bfloat16, one_chip)
    kv = _spec((8, CACHE, HKV, D), jnp.bfloat16, one_chip)
    lengths = _spec((8,), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda q, k, v, n: decode_attention(q, k, v, n, interpret=False)
    ).lower(q, kv, kv, lengths).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_flash", [False, True])
def test_published_width_decode_step_compiles_for_v5e(one_chip, monkeypatch,
                                                      use_flash):
    # kernels/ops.py runs the kernels in the interpreter on a CPU backend;
    # steer it to the chip's branch, as on a TPU host
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = SC.replace(dtype="bfloat16", use_flash=use_flash)
    shape = InputShape("serve_b8", CACHE, 8, "decode")

    def placed(tree):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip), tree)

    params = jax.eval_shape(lambda k: api.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    compiled = jax.jit(steps.make_serve_step(cfg, shape)).lower(
        placed(params), placed(steps.batch_specs(cfg, shape)),
        placed(steps.cache_specs(cfg, shape))).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_flash
    # bf16 weights and cache fit one 16 GB chip with room to spare
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 8 * 2**30
