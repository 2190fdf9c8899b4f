"""The hybrid family is the published Zamba2 block: the program against the
plain float32 reference of the benchmark (``chipbench/reference/zamba2.py``)
on the same seeded weights, at the registry's smoke size in float32.

Tolerance: logits within 1e-5.  Both sides compute in float32; the
program's products at the CPU's default precision against the reference's
``highest`` ones differ by about 5e-7 over the 7 layers.  The block's parts
move the logits far more: leaving out the per-application adapters moves
them by about 4e-4, the shared blocks' output by 2e-3, and swapping the
two shared blocks by 3e-3.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import weights as W  # noqa: E402
from chipbench.reference import zamba2 as Z  # noqa: E402
from repro.configs import resolve  # noqa: E402
from repro.models import (decode_step, init_cache, init_model,  # noqa: E402
                          model_forward, prefill)

TOL = 1e-5
CONFIG = json.loads((ROOT / "chipbench" / "tests" / "data"
                     / "zamba2-smoke.json").read_text())
CFG = resolve("zamba2-7b", smoke=True)
T = 20


@pytest.fixture(scope="module")
def seeded():
    params = jax.jit(lambda k: Z.weights(CONFIG, k))(W.seed_key(2 ** 33 + 7))
    toks = jax.random.randint(jax.random.PRNGKey(1), (T,), 0,
                              CFG.vocab_size)
    return params, toks, np.asarray(Z.logits(params, toks, CONFIG))


def test_smoke_config_is_the_published_structure():
    assert (CFG.family, CFG.num_mem_blocks, CFG.ssm_groups, CFG.adapter_rank,
            CFG.hybrid_layer_ids, CFG.hd(), CFG.act) == (
        "hybrid", 2, 2, 8, (1, 3, 5), 2 * CFG.d_model // CFG.num_heads,
        "gelu_exact")
    # the seeded weights are the program's own tree
    assert jax.tree.structure(jax.eval_shape(
        lambda: Z.weights(CONFIG, W.seed_key(0)))) == jax.tree.structure(
        jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), CFG)))


def test_forward_matches_reference(seeded):
    params, toks, ref = seeded
    lg, _ = jax.jit(lambda p, t: model_forward(p, CFG, t))(params, toks[None])
    np.testing.assert_allclose(np.asarray(lg[0]), ref, rtol=0, atol=TOL)


@pytest.mark.parametrize("bucket", [16, 13], ids=["bucketed", "exact"])
def test_prefill_then_decode_matches_reference(seeded, bucket):
    """A 13-token prompt prefilled in a bucket of 16 (right padding) or at
    its exact length, then decoded one token at a time through the cache:
    every logit against the reference's full forward at that position."""
    params, toks, ref = seeded
    n = 13
    pad = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(toks[:n])
    cache = init_cache(CFG, 1, 32, jnp.float32)
    lg, st = jax.jit(lambda p, t, c: prefill(p, CFG, t, c, true_len=n))(
        params, pad, cache)
    got = [lg[0, -1]]
    dec = jax.jit(lambda p, t, s: decode_step(p, CFG, t, s))
    for i in range(n, T - 1):
        lg, st = dec(params, toks[None, i:i + 1], st)
        got.append(lg[0, -1])
    np.testing.assert_allclose(np.asarray(jnp.stack(got)), ref[n - 1:T - 1],
                               rtol=0, atol=TOL)


def test_heads_last_state_serves_what_the_forward_gives():
    """The smoke block with 16 heads of 8 and a state of 8, so the SSM
    state is stored heads-last (the published Zamba2-7B's order): a
    13-token prompt prefilled in a bucket of 16, then decoded through the
    cache, gives every logit the full forward gives at that position.
    These weights give logits up to about 57, where float32's spacing is
    3.8e-6: both storage orders read 1.5e-5 off the forward, so the
    tolerance is relative (1e-6) on top of ``TOL``."""
    import dataclasses
    from repro.models.ssm import ssm_heads_last
    cfg = dataclasses.replace(CFG, ssm_head_dim=8, ssm_state=8)
    assert ssm_heads_last(cfg) and cfg.ssm_heads() == 16
    params = init_model(jax.random.PRNGKey(5), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(6), (T,), 0, cfg.vocab_size)
    ref, _ = jax.jit(lambda p, t: model_forward(p, cfg, t))(params,
                                                           toks[None])
    n = 13
    pad = jnp.zeros((1, 16), jnp.int32).at[0, :n].set(toks[:n])
    cache = init_cache(cfg, 1, 32, jnp.float32)
    lg, st = jax.jit(lambda p, t, c: prefill(p, cfg, t, c, true_len=n))(
        params, pad, cache)
    got = [lg[0, -1]]
    dec = jax.jit(lambda p, t, s: decode_step(p, cfg, t, s))
    for i in range(n, T - 1):
        lg, st = dec(params, toks[None, i:i + 1], st)
        got.append(lg[0, -1])
    np.testing.assert_allclose(np.asarray(jnp.stack(got)),
                               np.asarray(ref[0, n - 1:T - 1]), rtol=1e-6,
                               atol=TOL)


@pytest.mark.parametrize("arch", ["zamba2-7b", "mamba2-780m"])
def test_padded_prefill_leaves_the_state_of_the_exact_one(arch):
    """Past the true length ``dt`` and the SSM input are 0, so the SSM
    state passes through the padding unchanged, and the conv state is read
    at the true end: a prompt of 11 padded to 24 leaves every layer's SSM
    and conv state as the exact-length prefill does (float32; the padded
    scan runs one more chunk, so they agree to rounding, 1e-6)."""
    cfg = resolve(arch, smoke=True)
    params = init_model(jax.random.PRNGKey(3), cfg)
    n = 11
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, n), 0,
                              cfg.vocab_size)
    states = []
    for width in (n, 24):
        pad = jnp.zeros((1, width), jnp.int32).at[:, :n].set(toks)
        _, st = prefill(params, cfg, pad, init_cache(cfg, 1, 32, jnp.float32),
                        true_len=n)
        states.append(st.cache["mamba"] if cfg.family == "hybrid"
                      else st.cache)
    exact, padded = states
    for name in ("ssm", "conv_x", "conv_B", "conv_C"):
        assert float(jnp.max(jnp.abs(exact[name]))) > 0, name
        np.testing.assert_allclose(np.asarray(padded[name]),
                                   np.asarray(exact[name]), rtol=0,
                                   atol=1e-6, err_msg=name)
