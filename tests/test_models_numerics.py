"""Numerics: blocked attention vs naive, banded SWA, distributed-decode
math, SSD chunked vs sequential recurrence, MoE routing conservation."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.models.attention import attention_xla, decode_attention
from repro.models.ssm import (ssd_chunked, ssd_decode_step, ssm_heads_axis,
                              ssm_heads_last)
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


@pytest.mark.parametrize("heads_last", [False, True],
                         ids=["state_last", "heads_last"])
def test_ssd_decode_continues_prefill(heads_last):
    """prefill(T) state + decode(1) == prefill(T+1) last output, with the
    state stored (b,H,P,S) or heads-last (b,P,S,H); two groups, so the
    group broadcast is in the step.  Both orders give the same new state."""
    rng = np.random.default_rng(3)
    b, T, H, P, S, G = 1, 32, 4, 8, 16, 2
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x = mk(b, T + 1, H, P)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(b, T + 1, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.2, 2.0, size=(H,)), jnp.float32)
    Bm, Cm = mk(b, T + 1, G, S), mk(b, T + 1, G, S)
    y_all, want_state = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    _, state = ssd_chunked(x[:, :T], dt[:, :T], A, Bm[:, :T], Cm[:, :T],
                           chunk=16)
    if heads_last:
        state = jnp.moveaxis(state, 1, -1)
    y1, new_state = ssd_decode_step(state, x[:, T], dt[:, T], A, Bm[:, T],
                                    Cm[:, T], heads_last=heads_last)
    if heads_last:
        new_state = jnp.moveaxis(new_state, -1, 1)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y_all[:, T]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(new_state), np.asarray(want_state),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch,state,heads_last", [
    ("zamba2-7b", None, True),     # H 112 fills 0.875 of 128 lanes, S 64 0.5
    ("mamba2-780m", None, False),  # S 128 fills 1.0, H 48 0.375
    ("zamba2-7b", 112, False),     # a tie, H 112 against S 112: state last
])
def test_ssm_state_layout_follows_the_lane_fill(arch, state, heads_last):
    """At published widths the state's minor axis is whichever of heads
    and state size fills a 128-lane row better, the state on a tie; the
    serve cache is stored in that order, and ``ssm_heads_axis`` (which the
    dry run shards over "model") finds its heads."""
    import dataclasses
    from repro.models import init_cache
    cfg = resolve(arch)
    if state is not None:
        cfg = dataclasses.replace(cfg, ssm_state=state)
    assert ssm_heads_last(cfg) is heads_last
    H, P, S = cfg.ssm_heads(), cfg.ssm_head_dim, cfg.ssm_state
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 16))
    ssm = cache["mamba"]["ssm"] if cfg.family == "hybrid" else cache["ssm"]
    assert ssm.shape == (cfg.num_layers, 2) + (
        (P, S, H) if heads_last else (H, P, S))
    assert ssm.shape[ssm_heads_axis(cfg)] == H


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


# ---------------------------------------------------------------------------
# decode's in-place cache write == the one-hot select it replaced
# ---------------------------------------------------------------------------

KV_FAMILIES = ("dense", "moe", "vlm", "audio", "hybrid")
KV_MAX_SEQ = 160
KV_STEPS = 3


def _onehot_attn(lp, h, cfg, lc, length, ekv):
    """One layer of the decode with its own (B, S, K, hd) cache in and out
    and the write as a one-hot select over all S positions."""
    from repro.models import attention as A
    from repro.models import transformer as T
    Bz = h.shape[0]
    positions = length[:, None]
    hn = T._norm(cfg, lp["ln1"], h)
    q, k, v = A.qkv(lp["attn"], hn, cfg, positions=positions, rope=True)
    hot = (jnp.arange(lc["k"].shape[1])[None, :]
           == length[:, None])[..., None, None]
    new = {n: jnp.where(hot, x.astype(lc[n].dtype), lc[n])
           for n, x in (("k", k), ("v", v))}
    o = A.decode_attention(q, new["k"], new["v"], length + 1,
                           window=cfg.sliding_window)
    h = h + o.reshape(Bz, 1, -1) @ lp["attn"]["wo"]
    if ekv is not None:
        hn = T._norm(cfg, lp["lnx"], h)
        qx, _, _ = A.qkv(lp["xattn"], hn, cfg, positions=positions,
                         rope=False)
        o = A.decode_attention(qx, ekv["k"], ekv["v"],
                               jnp.full((Bz,), ekv["k"].shape[1]))
        h = h + o.reshape(Bz, 1, -1) @ lp["xattn"]["wo"]
    h, _ = T._ffn(lp, h, cfg)
    return h, new


def _onehot_hybrid(params, cfg, h, cache, length):
    """The hybrid decode layer by layer, each application's whole cache
    selected into by one-hot and returned whole."""
    from repro.models import attention as A
    from repro.models import transformer as T
    kv, x0, mc = cache["attn"], h, cache["mamba"]

    def attend(j, q, k, v):
        lc = {n: a[j] for n, a in kv.items()}
        hot = (jnp.arange(lc["k"].shape[1])[None, :]
               == length[:, None])[..., None, None]
        new = {n: jnp.where(hot, x.astype(lc[n].dtype), lc[n])
               for n, x in (("k", k), ("v", v))}
        o = A.decode_attention(q, new["k"], new["v"], length + 1,
                               window=cfg.sliding_window,
                               scale=T._hybrid_scale(cfg))
        return o, new

    new = []
    for i in range(cfg.num_layers):
        t = jnp.zeros_like(h)
        if i in cfg.hybrid_layer_ids:
            t, n = T._shared_block(cfg, params["shared_attn"],
                                   cfg.hybrid_layer_ids.index(i), h, x0,
                                   length[:, None], attend)
            new.append(n)
        lp, st = jax.tree.map(lambda a: a[i], (params["blocks"], mc))
        h, st = T._hybrid_layer(cfg, lp, h, t, state=st)
        mc = jax.tree.map(lambda a, s: a.at[i].set(s), mc, st)
    ac = {n: jnp.stack([a[n] for a in new]) for n in kv}
    return h, {"mamba": mc, "attn": ac}


def _onehot_decode(params, cfg, token, state):
    """The decode as it was before the in-place write: every layer's cache
    row goes in through the scan's xs and comes back stacked as its ys."""
    from repro.models import layers as Lyr
    from repro.models import transformer as T
    from repro.models.blockstack import ShardedStack, scan_stack_cached
    h = Lyr.embed(params["embed"], token)
    length = state.length
    if cfg.family == "hybrid":
        h, cache = _onehot_hybrid(params, cfg, h, state.cache, length)
    else:
        xs = (state.cache,) if state.enc_kv is None \
            else (state.cache, state.enc_kv)

        def body(h, lp, x):
            return _onehot_attn(lp, h, cfg, x[0], length,
                                x[1] if len(x) == 2 else None)
        if isinstance(params["blocks"], ShardedStack):
            h, cache = scan_stack_cached(params["blocks"], h, xs, body)
        else:
            h, cache = jax.lax.scan(lambda h, x: body(h, x[0], x[1:]), h,
                                    (params["blocks"], *xs))
    h = T._norm(cfg, params["final_norm"], h)
    return Lyr.unembed(params["embed"], h), T.ServeState(
        cache=cache, length=length + 1, enc_kv=state.enc_kv)


def _kv_leaves(cfg, cache):
    return cache["attn"] if cfg.family == "hybrid" else cache


def _kv_setup(family, slots, seed=0):
    """Smoke config, weights, a state whose caches hold random values (so
    a stray write shows), and slots at 0, 7, max_seq - 1 and past max_seq."""
    from repro.models import init_model
    from repro.models.blockstack import family_smoke_archs
    from repro.serve.steps import _init_serve_state
    cfg = resolve(family_smoke_archs()[family], smoke=True)
    params = init_model(jax.random.PRNGKey(seed), cfg)
    state = _init_serve_state(cfg, slots, KV_MAX_SEQ)
    leaves, tdef = jax.tree.flatten(state)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [jax.random.normal(k, x.shape).astype(x.dtype)
              if jnp.issubdtype(x.dtype, jnp.floating) else x
              for k, x in zip(keys, leaves)]
    state = jax.tree.unflatten(tdef, leaves)
    lengths = ([0, 7, KV_MAX_SEQ - 1, KV_MAX_SEQ + 3] * slots)[:slots]
    state.length = jnp.asarray(lengths, jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 2),
                                (KV_STEPS, slots, 1), 0, cfg.vocab_size)
    return cfg, params, state, tokens


def _assert_steps_equal(cfg, run, ref, state, tokens):
    """``run`` and ``ref`` ((tok, state) -> (logits, state)) agree bitwise
    over the decode steps, and a slot at or past max_seq writes nothing."""
    a = b = state
    for t in range(tokens.shape[0]):
        before = jax.tree.map(np.asarray, _kv_leaves(cfg, a.cache))
        past = np.asarray(a.length) >= KV_MAX_SEQ
        la, a = run(tokens[t], a)
        lb, b = ref(tokens[t], b)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        for old, new in zip(jax.tree.leaves(before),
                            jax.tree.leaves(_kv_leaves(cfg, a.cache))):
            np.testing.assert_array_equal(np.asarray(new)[:, past],
                                          old[:, past])
        assert past.any()


@pytest.mark.parametrize("family", KV_FAMILIES)
def test_decode_inplace_write_matches_onehot_replicated(family):
    from repro.models import decode_step
    cfg, params, state, tokens = _kv_setup(family, slots=4)
    run = jax.jit(lambda p, t, s: decode_step(p, cfg, t, s))
    ref = jax.jit(lambda p, t, s: _onehot_decode(p, cfg, t, s))
    _assert_steps_equal(cfg, lambda t, s: run(params, t, s),
                        lambda t, s: ref(params, t, s), state, tokens)


def zero3_kv_case(family):
    """lane_zero3 hosting on a 4-device mesh: the hosted decode with the
    in-place write against the same hosting with the one-hot decode.  Runs
    in a process that sees 4 CPU devices."""
    import repro.serve.steps as steps
    from repro.serve import build_serve_step
    cfg, params, state, tokens = _kv_setup(family, slots=4)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]).reshape(2, 2, 1),
                             ("pod", "data", "model"))

    def hosted():
        step = build_serve_step(cfg, max_seq=KV_MAX_SEQ, slots=4,
                                hosting="lane_zero3", mesh=mesh)
        w, like = step.prepare(params), step.init_state()
        # the step donates its state: hand it a placed copy, keep ours
        return lambda t, s: step.decode(w, t, jax.tree.map(
            lambda x, z: jax.device_put(np.asarray(x), z.sharding), s,
            like))

    run = hosted()
    orig, steps.decode_step = steps.decode_step, _onehot_decode
    try:
        ref = hosted()
        ref(tokens[0], state)          # traces the one-hot decode
    finally:
        steps.decode_step = orig
    _assert_steps_equal(cfg, run, ref, state, tokens)


ZERO3_KV_FAMILIES = tuple(f for f in KV_FAMILIES if f != "hybrid")
_ZERO3_KV = None


def _zero3_kv_results():
    import os
    import pathlib
    import subprocess
    import sys
    here = pathlib.Path(__file__).resolve().parent
    code = ("import sys\n"
            "from repro.tuning.backend import apply_backend_setup\n"
            "apply_backend_setup('cpu', host_device_count=4)\n"
            "import test_models_numerics as t\n"
            "for f in sys.argv[1:]:\n"
            "    try:\n"
            "        t.zero3_kv_case(f)\n"
            "        print('PASS', f)\n"
            "    except Exception as e:\n"
            "        print('FAIL', f, repr(e)[:2000].replace('\\n', ' '))\n")
    path = [str(here.parent / "src"), str(here), os.environ.get("PYTHONPATH")]
    env = {**os.environ,
           "PYTHONPATH": os.pathsep.join(p for p in path if p)}
    proc = subprocess.run([sys.executable, "-c", code, *ZERO3_KV_FAMILIES],
                          capture_output=True, text=True, timeout=1200,
                          env=env)
    out = {line.split()[1]: line for line in proc.stdout.splitlines()
           if line.startswith(("PASS ", "FAIL "))}
    out["__stderr__"] = proc.stderr[-3000:]
    return out


@pytest.mark.parametrize("family", ZERO3_KV_FAMILIES)
def test_decode_inplace_write_matches_onehot_lane_zero3(family):
    global _ZERO3_KV
    if _ZERO3_KV is None:
        _ZERO3_KV = _zero3_kv_results()
    line = _ZERO3_KV.get(family, "no result:\n" + _ZERO3_KV["__stderr__"])
    assert line.startswith("PASS"), line


def test_compiled_decode_writes_the_cache_in_place():
    """The replicated decode (smoke dense config, 8 layers, 8 slots x 512
    positions, so one K stack outweighs one layer's own temporaries) holds
    no copy of the stacked cache, needs less temporary memory than one K
    stack, and returns the cache in the buffers it was donated."""
    import dataclasses
    import re
    from repro.models import init_model
    from repro.serve import build_serve_step
    cfg = dataclasses.replace(resolve("h2o-danube-3-4b", smoke=True),
                              num_layers=8)
    slots, max_seq = 8, 512
    step = build_serve_step(cfg, max_seq=max_seq, slots=slots)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(step.init_state)
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    compiled = step.decode.lower(params, tok, state).compile()
    k = state.cache["k"]
    assert compiled.memory_analysis().temp_size_in_bytes \
        < k.size * k.dtype.itemsize
    text = compiled.as_text()
    # outputs (logits, cache k, cache v, length); arguments (params, token,
    # cache k, cache v, length)
    first = len(jax.tree.leaves(params)) + 1
    alias = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}",
                            text.split("\n", 1)[0]))
    assert (alias.get("1"), alias.get("2")) == (str(first), str(first + 1))
    stack = re.escape("[" + ",".join(map(str, k.shape)) + "]")
    copies = [line for line in text.splitlines()
              if re.search(r"= \w+" + stack + r"\S* copy\(", line)]
    assert not copies, copies
