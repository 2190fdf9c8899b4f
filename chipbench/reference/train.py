"""The reference's first training steps: loss, gradient and the AdamW
update as the configuration and the mix state them.

AdamW (decoupled decay, global-norm clipping, linear warmup then cosine
to ``min_lr_frac``), all in float32.  Decay applies to every stored leaf
of rank >= 2: the layer stack stores each per-layer tensor with a
leading layer axis, so that is every leaf of the stack and the
embedding.  ``master`` is the type the parameters are kept in between
steps (the leaf's own type, or float32 masters); the forward always sees
each leaf in its own type.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import leaves, weights
from chipbench.reference import data, ssm

LOSSES = {"ssm": ssm.loss}


def lr_at(opt: dict, count: int) -> float:
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((count - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * t))
    return opt["lr"] * warm * frac


def _placement(devices):
    """``(rows, batch_sharding, state_sharding)`` over ``devices``: each
    pass takes one row per device; parameters, moments and the gradient
    sum split their leading axis over the devices where it divides."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    n = len(devices)
    if n == 1:
        return 1, None, None
    mesh = Mesh(np.asarray(devices), ("x",))

    def state(a):
        spec = P("x") if a.ndim and a.shape[0] % n == 0 else P()
        return NamedSharding(mesh, spec)
    return n, NamedSharding(mesh, P("x")), state


def follow(config: dict, traffic: dict, master: str, seed: int, *,
           steps: int, mode: str = "f32", rows: str = "all",
           devices=None) -> dict:
    """Run ``steps`` reference steps from the seed's weights on the seed's
    batches.  Returns {"losses": [float], "grad1": per-leaf norms of the
    first clipped gradient, "change": per-leaf norms of the parameters'
    change over the ``steps`` updates} (numpy vectors, ``leaves`` order).

    master: "leaf" keeps each leaf in its own type between steps,
    "float32" keeps float32 masters.  rows: "all", or "half" (a fault:
    the loss is the mean over the first half of each batch only).  The
    batch is taken a few rows at a time (one per device of ``devices``)
    and the gradients summed in float32, so the reference fits beside
    its optimizer state.
    """
    opt = traffic["optimizer"]
    lossf = LOSSES[config["family"]]
    per_pass, bsh, ssh = _placement(devices or jax.devices()[:1])
    key = weights.seed_key(seed)
    make = jax.jit(weights.make_fn(config))
    params = make(key)
    dtypes = jax.tree.map(lambda a: a.dtype, params)
    store = (lambda a: a) if master == "leaf" else \
        (lambda a: a.astype(master))
    put = (lambda t: t) if ssh is None else \
        (lambda t: jax.tree.map(lambda a: jax.device_put(a, ssh(a)), t))
    p = put(jax.tree.map(store, params))
    del params

    @jax.jit
    def grad_fn(p, tok, lab):
        fwd = jax.tree.map(lambda a, d: a.astype(d), p, dtypes)
        return jax.value_and_grad(
            lambda q: lossf(q, tok, lab, config, mode))(fwd)

    @partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, g):
        return jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(p, g, m, v, count, lr, passes):
        g = jax.tree.map(lambda x: x / passes, g)
        gsq = sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))
        scale = jnp.minimum(1.0, opt["clip_norm"]
                            / jnp.maximum(jnp.sqrt(gsq), 1e-9))
        b1, b2 = opt["b1"], opt["b2"]
        c1 = 1 - b1 ** count
        c2 = 1 - b2 ** count

        def one(p, g, m, v):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            step = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
            if p.ndim >= 2:
                step = step + opt["weight_decay"] * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v

        gnorms = leaves.norms(g) * scale
        out = jax.tree.map(one, p, g, m, v)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), gnorms

    zeros = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), t),
        out_shardings=None if ssh is None else jax.tree.map(ssh, p))
    m, v = zeros(p), zeros(p)
    losses, grad1 = [], None
    for s in range(steps):
        tok, lab = data.batch(config["vocab_size"], seed, traffic["seq"],
                              traffic["batch"], s)
        if rows == "half":
            tok, lab = tok[:len(tok) // 2], lab[:len(lab) // 2]
        acc, total = zeros(p), 0.0
        passes = len(tok) // per_pass
        for k in range(passes):
            t = jnp.asarray(tok[k * per_pass:(k + 1) * per_pass])
            lb = jnp.asarray(lab[k * per_pass:(k + 1) * per_pass])
            if bsh is not None:
                t, lb = jax.device_put(t, bsh), jax.device_put(lb, bsh)
            loss, g = grad_fn(p, t, lb)
            acc = accumulate(acc, g)
            del g
            total += float(loss)
        losses.append(total / passes)
        count = s + 1
        p, m, v, gnorms = update(p, acc, m, v, np.float32(count),
                                 np.float32(lr_at(opt, count)),
                                 np.float32(passes))
        if s == 0:
            grad1 = np.asarray(gnorms)
    del m, v
    change = np.asarray(jax.jit(leaves.diff_norms)(p, make(key)))
    return {"losses": losses, "grad1": grad1, "change": change}
