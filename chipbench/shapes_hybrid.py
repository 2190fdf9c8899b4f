"""Operations and bytes of a hybrid (Zamba2) prefill and decode step, from
the configuration's shapes (``reference/zamba2.py:sizes``).

As in ``shapes.py``, these count what the model requires, not what an
implementation does: no recomputation, no padding, no idle decode slots.
A matrix product of (m, k) by (k, n) is 2·m·k·n operations.  The shared
blocks run at every application, so their weights count once per
application; each application's adapter and projection count once.
"""
from __future__ import annotations

from chipbench.reference.zamba2 import sizes


def _width(c: dict) -> int:
    return 2 if c["dtype"] == "bfloat16" else 4


def mamba_layer_params(c: dict) -> int:
    """Matrix and conv parameters of one Mamba2 layer: the input
    projections (z, x, B, C, dt), the depthwise convs, ``out_proj``."""
    z = sizes(c)
    d, di, GS, Hm, W = z["d"], z["di"], z["G"] * z["S"], z["Hm"], z["W"]
    return d * (2 * di + 2 * GS + Hm) + W * (di + 2 * GS) + di * d


def block_params(c: dict) -> int:
    """Matrix parameters of one shared block: QKV over the 2·d input, the
    output projection, the gate/up and down projections."""
    z = sizes(c)
    return (z["da"] * (z["H"] + 2 * z["K"]) * z["hd"] + z["H"] * z["hd"]
            * z["d"] + z["d"] * 2 * z["f"] + z["f"] * z["d"])


def app_params(c: dict) -> int:
    """One application's own matrices: the adapter and the projection."""
    z = sizes(c)
    return z["d"] * z["r"] + z["r"] * 2 * z["f"] + z["d"] * z["d"]


def decode_weight_reads(c: dict) -> int:
    """Parameters one decode step reads: every Mamba layer, the shared
    block at each application with that application's own matrices, and
    the tied head (the embedding table)."""
    z = sizes(c)
    return (z["L"] * mamba_layer_params(c)
            + len(z["ids"]) * (block_params(c) + app_params(c))
            + z["V"] * z["d"])


def state_bytes_per_slot(c: dict) -> int:
    """One slot's recurrent state over every layer: the float32 SSM state
    (heads x head dim x state) and the float32 conv state (the last
    ``d_conv - 1`` inputs of x, B and C)."""
    z = sizes(c)
    ssm = z["Hm"] * z["P"] * z["S"]
    conv = (z["W"] - 1) * (z["di"] + 2 * z["G"] * z["S"])
    return z["L"] * (ssm + conv) * 4


def kv_bytes_per_position(c: dict) -> int:
    """K and V of one position over every application."""
    z = sizes(c)
    return 2 * len(z["ids"]) * z["K"] * z["hd"] * _width(c)


def decode_flops(c: dict, lengths) -> float:
    """One decode step for the active slots; ``lengths`` are the cache
    positions each attends to (its new token included).  Per slot: every
    matrix once per use (``decode_weight_reads``), the SSD state step
    (the outer-product update and the read-out, 2·P·S each per head and
    layer), and each application's attention over its positions."""
    z = sizes(c)
    per_slot = (2.0 * decode_weight_reads(c)
                + z["L"] * 4.0 * z["Hm"] * z["P"] * z["S"])
    attn = 4.0 * len(z["ids"]) * z["H"] * z["hd"]
    return sum(per_slot + attn * n for n in lengths)


def prefill_flops(c: dict, length: int) -> float:
    """One prompt of ``length`` true tokens: every matrix of
    ``decode_weight_reads`` but the head over every token, the SSD
    recurrence (the state update and read-out, 2·P·S each per head, layer
    and token), each application's causal attention (each token attends
    to itself and those before), and the head at the last position only."""
    z = sizes(c)
    mats = z["L"] * mamba_layer_params(c) \
        + len(z["ids"]) * (block_params(c) + app_params(c))
    ssd = z["L"] * 4.0 * z["Hm"] * z["P"] * z["S"]
    attn = 4.0 * len(z["ids"]) * z["H"] * z["hd"]
    return ((2.0 * mats + ssd) * length + 2.0 * z["V"] * z["d"]
            + attn * length * (length + 1) / 2)


def decode_bytes(c: dict, lengths) -> float:
    """One decode step: the weights (``decode_weight_reads``), each
    active slot's embedding row, its recurrent state read and written,
    and its K/V over its actual length (the positions before the new
    token read, the new one written)."""
    z = sizes(c)
    w = _width(c)
    return float(decode_weight_reads(c) * w
                 + len(lengths) * (z["d"] * w + 2 * state_bytes_per_slot(c))
                 + kv_bytes_per_position(c) * sum(lengths))
