"""Pallas kernel allclose sweeps (interpret=True on CPU) vs ref.py oracles:
shapes × dtypes × mask modes for flash attention; shapes × chunkings for
the SSD kernel."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.ssd import ssd_tpu
from repro.kernels import ref


def _qkv(B, H, K, Tq, Tk, hd, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, Tq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, K, Tk, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, K, Tk, hd)), dtype)
    return q, k, v


ATT_SHAPES = [
    # B, H, K, Tq, Tk, hd, bq, bk
    (1, 2, 2, 128, 128, 64, 64, 64),
    (2, 4, 2, 256, 256, 64, 128, 128),
    (1, 8, 2, 256, 512, 32, 128, 128),    # GQA G=4, cross lengths
    (1, 2, 1, 512, 512, 128, 256, 128),   # MQA
]


@pytest.mark.parametrize("shape", ATT_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["causal", "full", "window"])
def test_flash_attention_allclose(shape, dtype, mode):
    B, H, K, Tq, Tk, hd, bq, bk = shape
    if mode == "causal" and Tq != Tk:
        pytest.skip("causal requires square here")
    causal = mode == "causal"
    window = 96 if mode == "window" else 0
    q, k, v = _qkv(B, H, K, Tq, Tk, hd, dtype)
    out = flash_attention_tpu(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


SSD_SHAPES = [
    # b, H, T, P, S, chunk, hb
    (1, 4, 64, 32, 32, 16, 4),
    (2, 8, 128, 32, 64, 32, 4),
    (1, 8, 128, 64, 128, 64, 8),
    (2, 4, 96, 16, 16, 32, 2),
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_allclose(shape, dtype):
    b, H, T, P, S, chunk, hb = shape
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(b, H, T, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, size=(b, H, T)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(b, T, S)), dtype)
    Cm = jnp.asarray(rng.normal(size=(b, T, S)), dtype)
    y = ssd_tpu(x, dt, A, Bm, Cm, chunk=chunk, heads_blk=hb, interpret=True)
    want = ref.ssd_ref(x, dt, A, Bm, Cm)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_padding_kblocks():
    """nk not dividing Tk: trailing keys must be masked, not read OOB."""
    q, k, v = _qkv(1, 2, 2, 128, 96, 32, jnp.float32)
    out = flash_attention_tpu(q, k, v, causal=False, block_q=64, block_k=64,
                              interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pallas_switch_raises_off_tpu(monkeypatch):
    """REPRO_USE_PALLAS=1 on a backend that is not a TPU is an error, not
    a quiet fall back to the reference; interpret mode stays available."""
    from repro.kernels import ops
    assert jax.default_backend() != "tpu"
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    with pytest.raises(RuntimeError, match="not|backend"):
        ops.use_pallas()
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    assert ops.use_pallas()
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    assert not ops.use_pallas()
