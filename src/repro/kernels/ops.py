"""jit'd dispatch wrappers: Pallas kernel on TPU, lax/jnp path elsewhere.

The model code (models/attention.py, models/ssm.py) computes through the
portable lax formulations by default; set REPRO_USE_PALLAS=1 on a TPU
runtime (or =interpret for CPU correctness runs) to route the hot paths
through the kernels.  =1 on any other backend raises.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_tpu
from .ssd import ssd_tpu
from . import ref


def _mode() -> str:
    v = os.environ.get("REPRO_USE_PALLAS", "0").lower()
    if v in ("1", "true", "tpu"):
        return "tpu"
    if v == "interpret":
        return "interpret"
    return "off"


def use_pallas() -> bool:
    """Whether the kernels run.  Asking for the TPU kernels on another
    backend raises instead of quietly computing the reference."""
    m = _mode()
    if m == "tpu" and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"REPRO_USE_PALLAS asks for the TPU kernels but the backend is "
            f"{jax.default_backend()!r}; use REPRO_USE_PALLAS=interpret "
            f"for correctness runs off the chip")
    return m != "off"


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B,H,Tq,hd); k,v: (B,K,Tk,hd) — head-major convention."""
    if use_pallas():
        return flash_attention_tpu(q, k, v, causal=causal, window=window,
                                   interpret=_mode() == "interpret")
    return ref.attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, A, B, C, *, chunk: int = 64):
    """x: (b,H,T,P); dt: (b,H,T); A: (H,); B,C: (b,T,S)."""
    if use_pallas():
        # a head block is 8 sublanes, or all heads when 8 does not divide
        hb = 8 if x.shape[1] % 8 == 0 else x.shape[1]
        return ssd_tpu(x, dt, A, B, C, chunk=chunk, heads_blk=hb,
                       interpret=_mode() == "interpret")
    return ref.ssd_ref(x, dt, A, B, C)
