"""Serving engine integration: pipeline chaining, batching, reconfiguration."""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.mdp import Config
from repro.data import synthetic_lm_batches, synthetic_requests
from repro.models import api as models
from repro.serving import PipelineServer, StageServer


@pytest.fixture(scope="module")
def server():
    stages = [
        StageServer(
            "s0",
            [ARCHS["xlstm-125m"].smoke(), ARCHS["whisper-small"].smoke()],
            seed=0,
        ),
        StageServer(
            "s1",
            [ARCHS["llama3.2-1b"].smoke(), ARCHS["granite-moe-3b-a800m"].smoke()],
            seed=1,
        ),
    ]
    return PipelineServer(stages)


def test_requests_flow_through_all_stages(server):
    n0 = len(server.completed)
    for r in synthetic_requests(7, vocab=256, seq_len=32, seed=0):
        server.submit(r)
    done = server.process()
    new = done[n0:]
    assert len(new) == 7
    for req in new:
        assert len(req.stage_outputs) == 2
        assert req.result.shape == (32,)


def test_reconfigure_switches_variant(server):
    server.apply_config(Config(z=(1, 0), f=(2, 1), b=(2, 8)))
    assert server.stages[0].z == 1
    assert server.stages[0].batcher.batch_size == 2
    assert server.stages[1].batcher.batch_size == 8
    assert server.switch_count >= 1
    for r in synthetic_requests(3, vocab=256, seq_len=32, seed=1):
        server.submit(r)
    before = len(server.completed)
    server.process()
    assert len(server.completed) - before == 3


def test_batcher_dispatches_actual_size():
    """Tail batches dispatch at their real size — no padded phantom rows."""
    from repro.serving.batcher import Batcher, Request
    b = Batcher(4, 8)
    b.put(Request(rid=0, tokens=np.arange(8, dtype=np.int32)))
    reqs, toks = b.next_batch()
    assert len(reqs) == 1
    assert toks.shape == (1, 8)              # actual batch, not batch_size
    assert (toks[0] == np.arange(8)).all()
    # short prompts zero-pad the sequence dimension only
    b.put(Request(rid=1, tokens=np.arange(3, dtype=np.int32)))
    b.put(Request(rid=2, tokens=np.arange(8, dtype=np.int32)))
    reqs, toks = b.next_batch()
    assert toks.shape == (2, 8)
    assert (toks[0, 3:] == 0).all()


def test_data_pipeline_learnable_and_deterministic():
    g1 = synthetic_lm_batches(vocab=128, seq_len=16, batch=4, seed=3)
    g2 = synthetic_lm_batches(vocab=128, seq_len=16, batch=4, seed=3)
    b1, b2 = next(g1), next(g2)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    assert np.array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # structured: token distribution far from uniform
    _, counts = np.unique(b1["tokens"], return_counts=True)
    assert counts.max() > 3 * counts.mean()


# ----------------------------------------------- stub inputs and host tokens --

def _fresh_frames(cfg, B):
    """Whisper's stub frames drawn op by op from their fixed key."""
    return jax.random.normal(jax.random.PRNGKey(1),
                             (B, cfg.enc_len, cfg.d_model), cfg.param_dtype) * 0.02


def _served(srv, batch):
    """The served tokens of a plain jitted forward over ``batch``."""
    logits, _ = jax.jit(lambda p, b: models.forward(p, b, srv.cfg))(srv.weights(0), batch)
    return np.asarray(jnp.argmax(logits, axis=-1))


@pytest.fixture
def whisper():
    return StageServer("asr", [ARCHS["whisper-small"].smoke()], seq_len=16, seed=3)


def _tokens(B, seed=0):
    return np.random.default_rng(seed).integers(0, 1000, (B, 16)).astype(np.int32)


def _feeds(srv, key):
    """Wraps (z, B)'s kept executable so each batch it is fed is logged."""
    exe, stubs = srv._compiled[key]
    fed = []
    srv._compiled[key] = (lambda params, batch: (fed.append(batch), exe(params, batch))[1],
                          stubs)
    return fed


@pytest.mark.parametrize("B", [1, 3])
def test_kept_frames_are_bit_identical_to_a_fresh_draw(whisper, B):
    first = whisper.execute(0, _tokens(B))
    fed = _feeds(whisper, (0, B))
    again = whisper.execute(0, _tokens(B))
    (batch,) = fed
    fresh = np.asarray(_fresh_frames(whisper.cfg, B))
    assert batch["enc_states"].dtype == fresh.dtype
    assert np.array_equal(np.asarray(batch["enc_states"]).view(np.uint16),
                          fresh.view(np.uint16))
    np.testing.assert_array_equal(first, again)


@pytest.mark.parametrize("B", [1, 3])
def test_execute_equals_a_forward_over_make_batch(whisper, B):
    toks = _tokens(B, seed=B)
    whisper.execute(0, toks)                       # the miss: draws and compiles
    served = whisper.execute(0, toks)              # the hit: reuses the frames
    np.testing.assert_array_equal(
        served, _served(whisper, whisper._make_batch(toks, whisper.cfg)))


@pytest.mark.parametrize("B", [1, 3])
def test_a_second_call_draws_nothing(whisper, B, monkeypatch):
    normal = jax.random.normal
    draws = []

    def counted(*args, **kwargs):
        draws.append(args)
        return normal(*args, **kwargs)

    whisper.weights(0)                   # drawn with normal too, before the count
    monkeypatch.setattr(jax.random, "normal", counted)
    whisper.execute(0, _tokens(B))
    assert len(draws) == 1
    whisper.execute(0, _tokens(B, seed=1))
    assert len(draws) == 1
    assert whisper.stats()["stub_inputs"] == {"drawn": 1, "reused": 1}


@pytest.mark.parametrize("B", [1, 3])
def test_clearing_the_executables_releases_the_stub_inputs(whisper, B):
    whisper.execute(0, _tokens(B))
    (frames,) = whisper._compiled[(0, B)][1].values()
    held = weakref.ref(frames)
    del frames
    whisper.params.clear()
    whisper._compiled.clear()
    gc.collect()
    assert held() is None


def test_a_decoder_stage_keeps_no_stub_inputs_and_serves_as_before():
    srv = StageServer("code", [ARCHS["starcoder2-3b"].smoke()], seq_len=16, seed=4)
    toks = _tokens(3, seed=7)
    outs = [srv.execute(0, toks) for _ in range(2)]
    assert srv._compiled[(0, 3)][1] == {}
    assert srv.stats()["stub_inputs"] == {"drawn": 0, "reused": 0}
    # the tokens as every call uploaded them before: converted on the device
    before = _served(srv, {"tokens": jnp.asarray(toks % srv.cfg.vocab)})
    for out in outs:
        np.testing.assert_array_equal(out, before)
