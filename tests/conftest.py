import os
import sys

import pytest

# tests run on the single real CPU device (the 512-device override is
# exclusively dryrun.py's)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# make tests/_hyp.py (guarded hypothesis import) importable from test modules
sys.path.insert(0, os.path.dirname(__file__))


@pytest.fixture(scope="module")
def topo():
    """A described TPU v5e (nothing runs; the TPU compiler, installed here,
    compiles for it). Described inside a module-scoped fixture, never at
    import, so every pytest worker collects the same tests and only the one
    given a module that asks for it loads the TPU library."""
    import jax
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
    """One chip of the described v5e, as a sharding for ShapeDtypeStruct."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])
