"""Mamba2 / SSD (state-space duality) blocks — arXiv:2405.21060.

Layout follows the reference Mamba2: in_proj emits [z | x | B | C | dt],
a short depthwise conv over (x|B|C), SSD mixing, gated RMSNorm, out_proj.

The SSD core is the *chunked dual form*: intra-chunk attention-like matmul
plus an inter-chunk state recurrence (a scan over T/Q states of size
H×P×S).  Training/prefill use `ssd_chunked` (or the Pallas kernel via
repro.kernels.ops); decode advances an explicit (conv_state, ssm_state)
pair in O(1) per token — this is what makes the long_500k cells feasible.

TP sharding: heads shard over the "model" axis (in_proj columns for z/x/dt
are head-major), B and C are group-shared (n_groups=1 ⇒ replicated — they
are tiny), out_proj is row-parallel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from .layers import dense_init, rmsnorm, _dtype


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_mamba2(key, cfg: ModelConfig) -> dict:
    """Projections are kept as SEPARATE tensors (w_z/w_x/w_B/w_C/w_dt and
    per-stream convs) rather than one fused in_proj: the head-major streams
    (z, x, dt) then shard cleanly over the "model" axis while the tiny
    group-shared B/C streams stay replicated — a fused column layout would
    slice across component boundaries."""
    d, dt_ = cfg.d_model, _dtype(cfg)
    di, S, G, W = cfg.d_inner(), cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv_width
    H = cfg.ssm_heads()
    ks = jax.random.split(key, 9)
    p = {
        "w_z": dense_init(ks[0], (d, di), dt_),
        "w_x": dense_init(ks[1], (d, di), dt_),
        "w_B": dense_init(ks[2], (d, G * S), dt_),
        "w_C": dense_init(ks[3], (d, G * S), dt_),
        "w_dt": dense_init(ks[4], (d, H), dt_),
        "conv_x_w": dense_init(ks[5], (W, di), dt_, scale=0.5),
        "conv_x_b": jnp.zeros((di,), dt_),
        "conv_B_w": dense_init(ks[6], (W, G * S), dt_, scale=0.5),
        "conv_B_b": jnp.zeros((G * S,), dt_),
        "conv_C_w": dense_init(ks[7], (W, G * S), dt_, scale=0.5),
        "conv_C_b": jnp.zeros((G * S,), dt_),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[8], (H,),
                                       minval=jnp.log(1e-3),
                                       maxval=jnp.log(1e-1))))).astype(jnp.float32),
        "norm": {"scale": jnp.ones((di,), dt_)},
        "out_proj": dense_init(ks[4], (di, d), dt_),
    }
    return p


# ---------------------------------------------------------------------------
# SSD chunked scan (pure jnp — also the oracle for the Pallas kernel)
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, A, B, C, *, chunk: int, init_state=None):
    """SSD dual-form mixing.

    x:  (b, T, H, P)   per-head values
    dt: (b, T, H)      positive step sizes (already softplus'd + biased)
    A:  (H,)           negative decay rates (= -exp(A_log))
    B, C: (b, T, G, S) input/output projections (G groups broadcast to H)
    Returns (y (b,T,H,P), final_state (b,H,P,S)).
    """
    b, T, H, P = x.shape
    G, S = B.shape[2], B.shape[3]
    Q = min(chunk, T)
    T0 = T
    if T % Q:                        # pad tail with dt=0 ⇒ state-neutral
        pad = Q - T % Q
        z = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = z(x), z(dt), z(B), z(C)
        T = T + pad
    nc = T // Q
    rep = H // G

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = jnp.repeat(B.astype(jnp.float32), rep, axis=2)   # (b,T,H,S)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2)

    # chunked views
    xc = xf.reshape(b, nc, Q, H, P)
    dtc = dtf.reshape(b, nc, Q, H)
    Bc = Bf.reshape(b, nc, Q, H, S)
    Cc = Cf.reshape(b, nc, Q, H, S)

    da = dtc * A[None, None, None, :]                     # (b,nc,Q,H) ≤ 0
    cum = jnp.cumsum(da, axis=2)                          # within-chunk
    seg_end = cum[:, :, -1, :]                            # (b,nc,H)

    # ---- intra-chunk (attention-like, causal) ----
    # L[q1,q2] = exp(cum[q1]-cum[q2]) · (q1 ≥ q2).  The mask goes INSIDE
    # the exp: above the diagonal diff is positive and overflows to inf,
    # and where(mask, inf, 0) has a NaN gradient (0 · inf)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b,nc,Q,Q,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    Lmat = jnp.exp(jnp.where(causal[None, None, :, :, None], diff,
                             -jnp.inf))
    scores = jnp.einsum("bcqhs,bckhs->bcqkh", Cc, Bc) * Lmat
    y_intra = jnp.einsum("bcqkh,bckh,bckhp->bcqhp", scores, dtc, xc)

    # ---- chunk summaries & inter-chunk recurrence ----
    decay_to_end = jnp.exp(seg_end[:, :, None, :] - cum)  # (b,nc,Q,H)
    chunk_state = jnp.einsum("bcqhs,bcqh,bcqh,bcqhp->bchps",
                             Bc, dtc, decay_to_end, xc)   # (b,nc,H,P,S)

    s0 = (jnp.zeros((b, H, P, S), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def chunk_step(state, inp):
        cs, g = inp                                       # (b,H,P,S), (b,H)
        prev = state
        state = prev * jnp.exp(g)[:, :, None, None] + cs
        return state, prev

    (final_state, prevs) = lax.scan(
        chunk_step, s0,
        (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(seg_end, 1, 0)))
    prev_states = jnp.moveaxis(prevs, 0, 1)               # (b,nc,H,P,S)

    y_inter = jnp.einsum("bcqhs,bchps->bcqhp",
                         Cc * jnp.exp(cum)[..., None], prev_states)

    y = (y_intra + y_inter).reshape(b, T, H, P)[:, :T0]
    return y.astype(x.dtype), final_state


def ssm_heads_last(cfg: ModelConfig) -> bool:
    """Whether the f32 SSM state is stored heads-last, ``(B, P, S, H)``,
    rather than ``(B, H, P, S)``.  The chip tiles an array's minor axis
    over 128 lanes, so the minor axis is whichever of heads and state size
    fills a row better (``n / roundup(n, 128)``), the state size on a tie:
    Zamba2-7B's 112 heads (0.875) beat its state of 64 (0.5), Mamba2-780m's
    state of 128 (1.0) beats its 48 heads (0.375).  The state keeps that
    one layout from the cache through every layer loop: a decode whose
    loops took another layout than its argument relaid the whole stack on
    the way in and out."""
    fill = lambda n: n / (-(-n // 128) * 128)
    return fill(cfg.ssm_heads()) > fill(cfg.ssm_state)


def ssm_heads_axis(cfg: ModelConfig) -> int:
    """The SSM state's heads axis, counted from the end: the same for one
    layer's ``(B, ...)`` state and the stacked ``(L, B, ...)`` cache."""
    return -1 if ssm_heads_last(cfg) else -3


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t, *, heads_last=False):
    """One-token SSD update.

    state: (b,H,P,S), or (b,P,S,H) when ``heads_last``; x_t: (b,H,P);
    dt_t: (b,H); B_t/C_t: (b,G,S).  Returns (y_t (b,H,P), new_state).
    Heads-last, the update is a broadcast product and the read-out a sum
    over S, so the state keeps its order through the step.
    """
    H = x_t.shape[1]
    G = B_t.shape[1]
    rep = H // G
    dt = dt_t.astype(jnp.float32)
    da = dt * A[None, :]                                   # (b,H)
    if heads_last:
        Bs = jnp.repeat(jnp.swapaxes(B_t.astype(jnp.float32), 1, 2), rep,
                        axis=2)                             # (b,S,H)
        Cs = jnp.repeat(jnp.swapaxes(C_t.astype(jnp.float32), 1, 2), rep,
                        axis=2)
        dtx = jnp.swapaxes(dt[:, :, None] * x_t.astype(jnp.float32), 1, 2)
        new_state = (state * jnp.exp(da)[:, None, None, :]
                     + dtx[:, :, None, :] * Bs[:, None])     # (b,P,S,H)
        y = jnp.swapaxes(jnp.sum(Cs[:, None] * new_state, axis=2), 1, 2)
        return y.astype(x_t.dtype), new_state
    Bh = jnp.repeat(B_t.astype(jnp.float32), rep, axis=1)  # (b,H,S)
    Ch = jnp.repeat(C_t.astype(jnp.float32), rep, axis=1)
    new_state = (state * jnp.exp(da)[:, :, None, None]
                 + jnp.einsum("bh,bhs,bhp->bhps", dt, Bh,
                              x_t.astype(jnp.float32)))
    y = jnp.einsum("bhs,bhps->bhp", Ch, new_state)
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# full block
# ---------------------------------------------------------------------------

def _conv1d(xBC, w, b, conv_state=None, length=None):
    """Depthwise causal conv, width W.  xBC: (B,T,C); w: (W,C).

    If conv_state (B, W-1, C) is given, it prefixes the sequence
    (decode/prefill continuation) and the updated state is returned: the
    last W-1 inputs, or with ``length`` (B,) the W-1 inputs before each
    row's true end (a right-padded prompt).
    """
    W = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((xBC.shape[0], W - 1, xBC.shape[2]), xBC.dtype)
    else:
        pad = conv_state.astype(xBC.dtype)
    full = jnp.concatenate([pad, xBC], axis=1)            # (B, T+W-1, C)
    out = sum(full[:, i:i + xBC.shape[1]] * w[i][None, None]
              for i in range(W))
    if W == 1:
        new_state = pad
    elif length is None:
        new_state = full[:, -(W - 1):]
    else:
        new_state = jax.vmap(lambda f, n: lax.dynamic_slice_in_dim(
            f, n, W - 1, axis=0))(full, length)
    return out + b[None, None], new_state


def gated_norm(p: dict, y, z, groups: int, eps: float):
    """Mamba2's gated RMSNorm: ``y * silu(z)`` normalised over each of
    ``groups`` equal channel groups (the published ``RMSNormGated`` with
    ``group_size = d_inner // ngroups``), then scaled."""
    g = y * jax.nn.silu(z)
    if groups == 1:
        return rmsnorm(p, g, eps)
    shape = g.shape
    gf = g.astype(jnp.float32).reshape(*shape[:-1], groups, -1)
    var = jnp.mean(jnp.square(gf), axis=-1, keepdims=True)
    out = (gf * jax.lax.rsqrt(var + eps)).reshape(shape)
    return (out * p["scale"].astype(jnp.float32)).astype(g.dtype)


def mamba2_block(p: dict, x, cfg: ModelConfig, *, state=None, length=None):
    """x: (B, T, d) → (B, T, d).  state: None (train) or serve-state dict.

    ``length`` (B,) marks a prefill's true prompt lengths where ``x`` is
    right-padded to a bucket: past it ``dt`` and the SSM input are 0, so
    ``exp(dt·A) = 1`` and the state passes through the padding unchanged,
    and the conv state is read at the true end.  Named scopes: ``mamba``
    (the whole mixer), ``ssd`` inside it (the chunked scan, or the decode's
    state step)."""
    with jax.named_scope("mamba"):
        return _mamba2(p, x, cfg, state, length)


def _mamba2(p: dict, x, cfg: ModelConfig, state, length):
    Bsz, T, _ = x.shape
    H, P = cfg.ssm_heads(), cfg.ssm_head_dim
    G, S = cfg.ssm_groups, cfg.ssm_state
    masked = state is not None and T > 1 and length is not None
    conv_len = length if masked else None
    z = x @ p["w_z"]
    xs = x @ p["w_x"]
    Bp = x @ p["w_B"]
    Cp = x @ p["w_C"]
    dt = x @ p["w_dt"]
    cs = state if state is not None else {}
    xs, new_cx = _conv1d(xs, p["conv_x_w"], p["conv_x_b"], cs.get("conv_x"),
                         conv_len)
    Bp, new_cB = _conv1d(Bp, p["conv_B_w"], p["conv_B_b"], cs.get("conv_B"),
                         conv_len)
    Cp, new_cC = _conv1d(Cp, p["conv_C_w"], p["conv_C_b"], cs.get("conv_C"),
                         conv_len)
    xs, Bp, Cp = jax.nn.silu(xs), jax.nn.silu(Bp), jax.nn.silu(Cp)
    xs = xs.reshape(Bsz, T, H, P)
    Bp = Bp.reshape(Bsz, T, G, S)
    Cp = Cp.reshape(Bsz, T, G, S)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None])
    A = -jnp.exp(p["A_log"])
    if masked:
        valid = jnp.arange(T)[None, :] < length[:, None]        # (B, T)
        dt = jnp.where(valid[..., None], dt, 0.0)
        xs = jnp.where(valid[..., None, None], xs, jnp.zeros((), xs.dtype))

    heads_last = ssm_heads_last(cfg)
    with jax.named_scope("ssd"):
        if state is None:
            y, _ = ssd_chunked(xs, dt, A, Bp, Cp, chunk=cfg.ssm_chunk)
            new_state = None
        elif T == 1:
            y1, new_ssm = ssd_decode_step(state["ssm"], xs[:, 0], dt[:, 0],
                                          A, Bp[:, 0], Cp[:, 0],
                                          heads_last=heads_last)
            y = y1[:, None]
        else:  # prefill with state capture, the scan's state (b,H,P,S)
            s0 = state["ssm"]
            y, new_ssm = ssd_chunked(
                xs, dt, A, Bp, Cp, chunk=cfg.ssm_chunk,
                init_state=jnp.moveaxis(s0, -1, 1) if heads_last else s0)
            if heads_last:
                new_ssm = jnp.moveaxis(new_ssm, 1, -1)
    if state is not None:
        new_state = {"conv_x": new_cx, "conv_B": new_cB, "conv_C": new_cC,
                     "ssm": new_ssm}

    y = y + xs * p["D"][None, None, :, None]          # fp32 D promotes…
    y = y.reshape(Bsz, T, cfg.d_inner()).astype(x.dtype)  # …cast back
    y = gated_norm(p["norm"], y, z, G, cfg.norm_eps)
    return y @ p["out_proj"], new_state


def init_mamba_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> dict:
    di, S, G, W = cfg.d_inner(), cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv_width
    H, P = cfg.ssm_heads(), cfg.ssm_head_dim
    return {
        "conv_x": jnp.zeros((batch, W - 1, di), dtype),
        "conv_B": jnp.zeros((batch, W - 1, G * S), dtype),
        "conv_C": jnp.zeros((batch, W - 1, G * S), dtype),
        "ssm": jnp.zeros((batch, P, S, H) if ssm_heads_last(cfg)
                         else (batch, H, P, S), jnp.float32),
    }
