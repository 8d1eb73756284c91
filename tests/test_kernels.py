"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles,
in interpret mode (kernel bodies execute on CPU)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def rand(shape, dtype):
    return jax.random.normal(KEY, shape, jnp.float32).astype(dtype)


FA_SHAPES = [
    # (B, S, H, Hkv, D)
    (1, 128, 4, 2, 64),
    (2, 256, 8, 8, 64),
    (1, 256, 6, 2, 128),
    (2, 128, 4, 1, 80),  # non-128 head_dim (zamba2-style)
]


@pytest.mark.parametrize("B,S,H,Hkv,D", FA_SHAPES)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3), (jnp.bfloat16, 4e-2)])
def test_flash_attention_causal(B, S, H, Hkv, D, dtype, tol):
    q = rand((B, S, H, D), dtype)
    k = rand((B, S, Hkv, D), dtype)
    v = rand((B, S, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    assert out.shape == want.shape and out.dtype == want.dtype
    assert jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32)).max() < tol


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_window(window):
    q = rand((1, 256, 4, 64), jnp.float32)
    k = rand((1, 256, 2, 64), jnp.float32)
    v = rand((1, 256, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    assert jnp.abs(out - want).max() < 2e-3


@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_shape_invariance(bq, bk):
    q = rand((1, 256, 4, 64), jnp.float32)
    k = rand((1, 256, 4, 64), jnp.float32)
    v = rand((1, 256, 4, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, bq=bq, bk=bk)
    want = ref.flash_attention_ref(q, k, v)
    assert jnp.abs(out - want).max() < 2e-3


DEC_SHAPES = [
    # (B, H, Hkv, D, C, n_valid)
    (2, 8, 2, 64, 1024, 700),
    (1, 24, 8, 128, 2048, 2048),
    (4, 4, 4, 64, 512, 100),
    (2, 32, 8, 128, 1024, 1),  # single valid slot
]


@pytest.mark.parametrize("B,H,Hkv,D,C,nv", DEC_SHAPES)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3), (jnp.bfloat16, 4e-2)])
def test_decode_attention(B, H, Hkv, D, C, nv, dtype, tol):
    q = rand((B, 1, H, D), dtype)
    k = rand((B, C, Hkv, D), dtype)
    v = rand((B, C, Hkv, D), dtype)
    lengths = jnp.full((B,), nv)
    out = ops.decode_attention(q, k, v, lengths)
    want = ref.decode_attention_ref(q, k, v, lengths)
    assert out.shape == want.shape
    assert jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32)).max() < tol


def test_decode_attention_ragged_batch():
    """Each sequence has a different valid length (real serving batch)."""
    B, H, Hkv, D, C = 3, 8, 4, 64, 512
    q = rand((B, 1, H, D), jnp.float32)
    k = rand((B, C, Hkv, D), jnp.float32)
    v = rand((B, C, Hkv, D), jnp.float32)
    lengths = jnp.array([37, 512, 256])
    out = ops.decode_attention(q, k, v, lengths)
    want = ref.decode_attention_ref(q, k, v, lengths)
    assert jnp.abs(out - want).max() < 2e-3


def test_flash_matches_model_attention_path():
    """cfg.use_flash=True routes model attention through the kernels and
    must reproduce the jnp path."""
    from repro.configs import ARCHS
    from repro.models import api
    cfg = ARCHS["llama3.2-1b"].smoke().replace(
        d_model=256,
        n_heads=4,
        n_kv=2,
        n_layers=2,
    )
    cfg_f = cfg.replace(use_flash=True)
    p = api.init_model(KEY, cfg)
    batch = {"tokens": jnp.arange(2 * 128).reshape(2, 128) % cfg.vocab}
    lg, _ = api.forward(p, batch, cfg)
    lf, _ = api.forward(p, batch, cfg_f)
    assert jnp.abs(lg - lf).max() < 5e-3
