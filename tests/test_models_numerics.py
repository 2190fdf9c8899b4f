"""Numerics: blocked attention vs naive, banded SWA, distributed-decode
math, SSD chunked vs sequential recurrence, MoE routing conservation."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.models.attention import attention_xla, decode_attention
from repro.models.ssm import ssd_chunked, ssd_decode_step
from repro.models import moe as M
from repro.kernels import ref as kref
from repro.configs import resolve


def _mk(B, H, K, Tq, Tk, hd, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Tq, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Tk, K, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Tk, K, hd)), jnp.float32)
    return q, k, v


def _to_ref(x):
    return jnp.swapaxes(x, 1, 2)    # (B,T,H,hd) → (B,H,T,hd)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(64, 64), (128, 32), (37, 64)])
def test_attention_xla_matches_naive(causal, blocks):
    bq, bk = blocks
    q, k, v = _mk(2, 4, 2, 128, 128, 32)
    out = attention_xla(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = kref.attention_ref(_to_ref(q), _to_ref(k), _to_ref(v),
                              causal=causal)
    np.testing.assert_allclose(np.asarray(_to_ref(out)), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [16, 48, 100])
def test_banded_swa_matches_masked(window):
    q, k, v = _mk(1, 4, 2, 128, 128, 32)
    out = attention_xla(q, k, v, causal=True, window=window, block_q=32)
    want = kref.attention_ref(_to_ref(q), _to_ref(k), _to_ref(v),
                              causal=True, window=window)
    np.testing.assert_allclose(np.asarray(_to_ref(out)), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_matches_full_attention():
    """decode_attention(q1, cache) == last row of full causal attention."""
    B, H, K, T, hd = 2, 4, 2, 64, 32
    q, k, v = _mk(B, H, K, T, T, hd)
    full = attention_xla(q, k, v, causal=True, block_q=32)
    out = decode_attention(q[:, -1:], k, v,
                           jnp.full((B,), T, jnp.int32))
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, -1]),
                               rtol=2e-5, atol=2e-5)


def test_decode_respects_length_mask():
    B, H, K, T, hd = 1, 2, 2, 32, 16
    q, k, v = _mk(B, H, K, T, T, hd)
    short = decode_attention(q[:, -1:], k, v, jnp.full((B,), 10, jnp.int32))
    trunc = decode_attention(q[:, -1:], k[:, :10], v[:, :10],
                             jnp.full((B,), 10, jnp.int32))
    np.testing.assert_allclose(np.asarray(short), np.asarray(trunc),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,chunk", [(64, 16), (64, 64), (96, 32), (50, 16)])
def test_ssd_chunked_matches_recurrence(T, chunk):
    rng = np.random.default_rng(2)
    b, H, P, S, G = 2, 4, 16, 24, 1
    x = jnp.asarray(rng.normal(size=(b, T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(b, T, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.2, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(b, T, G, S)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(b, T, G, S)), jnp.float32)
    y, state = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    want = kref.ssd_ref(jnp.swapaxes(x, 1, 2),
                        jnp.moveaxis(dt, 1, 2), A, Bm[:, :, 0], Cm[:, :, 0])
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(y, 1, 2)),
                               np.asarray(want), rtol=2e-4, atol=2e-4)


def test_ssd_chunked_grad_finite_under_strong_decay():
    """A chunk's decay can sum past exp's f32 range (mamba2-780m at full
    width: dt near 1 with A down to -16 over 64 steps).  The masked upper
    triangle must not leak an inf into the gradient."""
    rng = np.random.default_rng(4)
    b, T, H, P, S = 1, 128, 2, 8, 16
    x = jnp.asarray(rng.normal(size=(b, T, H, P)), jnp.float32)
    dt = jnp.ones((b, T, H), jnp.float32)
    A = jnp.asarray([-16.0, -1.0], jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(b, T, 1, S)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(b, T, 1, S)), jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(ssd_chunked(*a, chunk=64)[0]),
                     argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()


def test_ssd_decode_continues_prefill():
    """prefill(T) state + decode(1) == prefill(T+1) last output."""
    rng = np.random.default_rng(3)
    b, T, H, P, S, G = 1, 32, 2, 8, 16, 1
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x = mk(b, T + 1, H, P)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(b, T + 1, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.2, 2.0, size=(H,)), jnp.float32)
    Bm, Cm = mk(b, T + 1, G, S), mk(b, T + 1, G, S)
    y_all, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    _, state = ssd_chunked(x[:, :T], dt[:, :T], A, Bm[:, :T], Cm[:, :T],
                           chunk=16)
    y1, _ = ssd_decode_step(state, x[:, T], dt[:, T], A, Bm[:, T], Cm[:, T])
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y_all[:, T]),
                               rtol=2e-4, atol=2e-4)


def test_moe_routing_weights_sum():
    """Kept tokens' routing weights renormalize to ≤1 and the layer output
    is a convex combination of expert outputs (capacity drops reduce it)."""
    cfg = resolve("granite-moe-3b-a800m", smoke=True)
    rng = np.random.default_rng(4)
    params = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)), jnp.float32)
    out, aux = M.moe_block(params, x, cfg)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) >= 0.99   # Switch aux loss ≥ 1 at uniform routing


def test_moe_capacity_overflow_drops_gracefully():
    import dataclasses
    cfg = dataclasses.replace(resolve("dbrx-132b", smoke=True),
                              moe_capacity_factor=0.25)
    params = M.init_moe(jax.random.PRNGKey(1), cfg)
    x = jnp.ones((1, 32, cfg.d_model), jnp.float32)   # all tokens identical
    out, _ = M.moe_block(params, x, cfg)              # severe overflow
    assert np.isfinite(np.asarray(out)).all()
