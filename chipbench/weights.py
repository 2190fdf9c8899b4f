"""The benchmark's own weights, drawn from ``--seed`` on the device.

One function per family builds the whole parameter tree in the tree
layout the program's models take (``embed``, ``final_norm``, a layer
stack ``blocks`` with a leading layer axis), each leaf drawn in the type
it is served in from a key folded from the seed and the leaf's name.
The program and the plain reference get the same values from the same
seed; nothing here imports the program.

Initialisation follows each family's published reference code:
  ssm    mamba_ssm: embedding N(0, 0.02); projections U(+-1/sqrt(fan_in));
         out_proj further divided by sqrt(n_layer); depthwise conv
         U(+-1/sqrt(width)); A in U[1, 16]; dt log-uniform in [1e-3, 1e-1]
         (dt_bias its inverse softplus); D and norm scales 1.
  dense  Llama (HF): every matrix and the embedding N(0, 0.02); norms 1.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def _key(root, name: str):
    return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _uniform(root, name, shape, dtype, bound):
    return jax.random.uniform(_key(root, name), shape, jnp.float32,
                              -bound, bound).astype(dtype)


def _normal(root, name, shape, dtype, std):
    return (std * jax.random.normal(_key(root, name), shape,
                                    jnp.float32)).astype(dtype)


def ssm_sizes(c: dict) -> dict:
    d = c["d_model"]
    di = c["expand"] * d
    return {"L": c["n_layer"], "d": d, "di": di, "S": c["d_state"],
            "G": c["ngroups"], "W": c["d_conv"], "H": di // c["headdim"],
            "P": c["headdim"], "V": c["vocab_size"], "Q": c["chunk_size"]}


def ssm_weights(c: dict, key) -> dict:
    z = ssm_sizes(c)
    L, d, di, S, G, W, H, V = (z[k] for k in "L d di S G W H V".split())
    dt = jnp.dtype(c["dtype"])
    proj = 1.0 / math.sqrt(d)
    m = {
        "w_z": _uniform(key, "w_z", (L, d, di), dt, proj),
        "w_x": _uniform(key, "w_x", (L, d, di), dt, proj),
        "w_B": _uniform(key, "w_B", (L, d, G * S), dt, proj),
        "w_C": _uniform(key, "w_C", (L, d, G * S), dt, proj),
        "w_dt": _uniform(key, "w_dt", (L, d, H), dt, proj),
        "conv_x_w": _uniform(key, "conv_x_w", (L, W, di), dt,
                             1 / math.sqrt(W)),
        "conv_x_b": _uniform(key, "conv_x_b", (L, di), dt, 1 / math.sqrt(W)),
        "conv_B_w": _uniform(key, "conv_B_w", (L, W, G * S), dt,
                             1 / math.sqrt(W)),
        "conv_B_b": _uniform(key, "conv_B_b", (L, G * S), dt,
                             1 / math.sqrt(W)),
        "conv_C_w": _uniform(key, "conv_C_w", (L, W, G * S), dt,
                             1 / math.sqrt(W)),
        "conv_C_b": _uniform(key, "conv_C_b", (L, G * S), dt,
                             1 / math.sqrt(W)),
        "A_log": jnp.log(jax.random.uniform(_key(key, "A"), (L, H),
                                            jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((L, H), jnp.float32),
        "dt_bias": _dt_bias(_key(key, "dt"), (L, H)),
        "norm": {"scale": jnp.ones((L, di), dt)},
        "out_proj": _uniform(key, "out_proj", (L, di, d), dt,
                             1.0 / math.sqrt(di) / math.sqrt(L)),
    }
    return {"embed": {"tok": _normal(key, "embed", (V, d), dt, 0.02)},
            "final_norm": {"scale": jnp.ones((d,), dt)},
            "blocks": {"ln1": {"scale": jnp.ones((L, d), dt)}, "mamba": m}}


def _dt_bias(key, shape):
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))          # softplus(dt_bias) == dt


def dense_sizes(c: dict) -> dict:
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"],
            "H": c["num_attention_heads"], "K": c["num_key_value_heads"],
            "hd": c["head_dim"], "f": c["intermediate_size"],
            "V": c["vocab_size"]}


def dense_weights(c: dict, key) -> dict:
    z = dense_sizes(c)
    L, d, H, K, hd, f, V = (z[k] for k in "L d H K hd f V".split())
    dt = jnp.dtype(c["dtype"])
    n = lambda name, shape: _normal(key, name, shape, dt, 0.02)
    blocks = {"ln1": {"scale": jnp.ones((L, d), dt)},
              "attn": {"wq": n("wq", (L, d, H * hd)),
                       "wk": n("wk", (L, d, K * hd)),
                       "wv": n("wv", (L, d, K * hd)),
                       "wo": n("wo", (L, H * hd, d))},
              "ln2": {"scale": jnp.ones((L, d), dt)},
              "mlp": {"w_up": n("w_up", (L, d, f)),
                      "w_gate": n("w_gate", (L, d, f)),
                      "w_down": n("w_down", (L, f, d))}}
    embed = {"tok": n("embed", (V, d))}
    if not c["tie_word_embeddings"]:
        embed["head"] = n("head", (d, V))
    return {"embed": embed, "final_norm": {"scale": jnp.ones((d,), dt)},
            "blocks": blocks}


FAMILIES = {"ssm": ssm_weights, "dense": dense_weights}


def make_fn(config: dict):
    """``fn(key) -> params`` for the configuration's family; jit it."""
    family = FAMILIES[config["family"]]
    return lambda key: family(config, key)


def seed_key(seed: int):
    """The root key of ``--seed`` (any whole number below 2**63)."""
    return jax.random.PRNGKey(int(seed))
