"""The Pallas kernels compile for a TPU v5e at the published model widths,
and the serving decode at the benchmark's size keeps its cache in place.

Nothing runs: each test lowers and compiles a kernel for a described (not
attached) ``v5e:2x2`` chip, which is what the chip's compiler would refuse
before any chip time is spent — block shapes off the (8, 128) tiling,
primitives the kernel lowering lacks, more VMEM than a kernel may use.
Interpret mode (tests/test_kernels.py) checks values; this file checks
that the same kernels exist for the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import resolve
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.ssd import ssd_tpu


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_at_h2o_danube_width(one_chip):
    cfg = resolve("h2o-danube-3-4b")
    H, K, hd, T = cfg.num_heads, cfg.num_kv_heads, cfg.hd(), 2048
    assert (H, K, hd) == (32, 8, 120)
    bf = jnp.bfloat16
    _compile(lambda q, k, v: flash_attention_tpu(
                 q, k, v, causal=True, window=cfg.sliding_window),
             one_chip, ((1, H, T, hd), bf), ((1, K, T, hd), bf),
             ((1, K, T, hd), bf))


def test_ssd_compiles_at_mamba2_780m_width(one_chip):
    cfg = resolve("mamba2-780m")
    H, P, S, T = cfg.ssm_heads(), cfg.ssm_head_dim, cfg.ssm_state, 2048
    assert (H, P, S) == (48, 64, 128)
    b, bf, f32 = 4, jnp.bfloat16, jnp.float32
    _compile(lambda x, dt, A, B, C: ssd_tpu(
                 x, dt, A, B, C, chunk=cfg.ssm_chunk, heads_blk=8),
             one_chip, ((b, H, T, P), bf), ((b, H, T), f32), ((H,), f32),
             ((b, T, S), bf), ((b, T, S), bf))


def test_decode_writes_the_cache_in_place_at_h2o_danube_width(one_chip):
    """The replicated decode at 16 slots x 2048 positions: no op but an
    in-place dynamic-update-slice makes a whole [layers, slots, S, K, hd]
    K or V stack (the chip keeps the stack with S minor; a write that
    made the compiler relayout it would copy all of it), and its
    temporaries stay far below one stack."""
    from repro.models import init_model
    from repro.serve import build_serve_step
    cfg = resolve("h2o-danube-3-4b")
    slots, max_seq = 16, 2048
    step = build_serve_step(cfg, max_seq=max_seq, slots=slots)
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg)))
    state = on_chip(jax.eval_shape(step.init_state))
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    compiled = step.decode.lower(params, tok, state).compile()
    k = state.cache["k"]
    assert k.shape == (24, 16, 2048, 8, 120)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < k.size * k.dtype.itemsize // 100
    stack = re.escape("[" + ",".join(map(str, k.shape)) + "]")
    ops = set(re.findall(r"= bf16" + stack + r"\S* ([\w-]+)\(",
                         compiled.as_text()))
    assert ops <= {"parameter", "get-tuple-element",
                   "dynamic-update-slice"}, ops


@pytest.fixture(scope="module")
def hybrid_decode(one_chip):
    """The replicated hybrid decode at the benchmark's cut (24 layers,
    applications at 6, 11, 17, 23) and 16 slots x 2048, compiled for one
    described v5e: (its serve state's shapes, the compiled program)."""
    import dataclasses
    from repro.models import init_model
    from repro.serve import build_serve_step
    cfg = dataclasses.replace(resolve("zamba2-7b"), num_layers=24,
                              hybrid_layer_ids=(6, 11, 17, 23))
    slots, max_seq = 16, 2048
    step = build_serve_step(cfg, max_seq=max_seq, slots=slots)
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg)))
    state = on_chip(jax.eval_shape(step.init_state))
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    return state, step.decode.lower(params, tok, state).compile()


def test_hybrid_decode_keeps_its_caches_in_place_at_zamba2_width(
        hybrid_decode):
    """No op but an in-place dynamic-update-slice makes the whole
    (applications, slots, S, K, hd) K or V stack, and the temporaries stay
    under 2.5e9 B.  A layer loop that picks the application by a
    conditional copied the whole K/V stack (5.7e9 B of temporaries),
    slices of the weight stacks taken outside the loops copied them
    (3.4e9 B), and the f32 SSM state stored with S minor was relaid in
    and out of the loops (2 x 0.705e9 B); what is left is 0.42e9 B."""
    state, compiled = hybrid_decode
    k = state.cache["attn"]["k"]
    assert k.shape == (4, 16, 2048, 32, 224)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9
    stack = re.escape("[" + ",".join(map(str, k.shape)) + "]")
    ops = set(re.findall(r"= bf16" + stack + r"\S* ([\w-]+)\(",
                         compiled.as_text()))
    assert ops <= {"parameter", "get-tuple-element",
                   "dynamic-update-slice"}, ops


def test_hybrid_decode_keeps_the_ssm_state_in_one_layout_at_zamba2_width(
        hybrid_decode):
    """The f32 SSM state is stored heads-last, (layers, slots, P, S, H):
    112 heads fill 112 of the chip's 128 lanes where S = 64 fills half.
    Stored (.., H, P, S), the argument took the chip's heads-minor layout
    while the middle layer loops ran S-minor, and two copies of the whole
    stack (about 3.4 ms each on the chip) converted it between them.  Now
    every value shaped like the stack has one layout, no copy makes it,
    and the decode's temporaries stay under 0.6e9 B (0.42e9 measured,
    1.95e9 with the copies)."""
    state, compiled = hybrid_decode
    ssm = state.cache["mamba"]["ssm"]
    assert ssm.shape == (24, 16, 64, 64, 112)
    stack = re.escape("[" + ",".join(map(str, ssm.shape)) + "]")
    made = re.findall(r"= f32" + stack + r"(\{[^}]*\})? ([\w-]+)\(",
                      compiled.as_text())
    assert made
    assert len({layout for layout, _ in made}) == 1, made
    assert "copy" not in {op for _, op in made}, made
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9
