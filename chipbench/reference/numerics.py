"""Matrix products of the references, in the precision a run asks for.

  f32   float32 operands, every product at ``Precision.HIGHEST``
  bf16  operands rounded to bfloat16, float32 accumulation
  fp8   operands scaled per tensor into float8_e4m3fn's range and
        rounded to it, float32 accumulation (the control)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")
_HI = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0


def quantize(x, mode: str):
    x = x.astype(jnp.float32)
    if mode == "f32":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        s = jax.lax.stop_gradient(s)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown precision {mode!r} (have {MODES})")


def einsum(eq: str, *ops, mode: str = "f32"):
    return jnp.einsum(eq, *(quantize(o, mode) for o in ops), precision=_HI,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def cross_entropy_sum(logits, labels):
    """Summed next-token cross entropy of (T, V) float32 logits."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)
