"""Operations and bytes that the work needs, from the configuration's shapes.

These count what the model requires, not what an implementation does:
no recomputation, no padding, no idle decode slots.  A matrix product of
(m, k) by (k, n) is 2·m·k·n operations.
"""
from __future__ import annotations

from chipbench.weights import dense_sizes, ssm_sizes


def ssm_forward_flops_per_token(c: dict) -> float:
    """Mamba2: the projections (the published fused in_proj and out_proj),
    the depthwise conv, the SSD chunked dual form (intra-chunk scores and
    values over whole Q x Q chunk blocks, chunk states, the inter-chunk
    read-out) and the tied head."""
    z = ssm_sizes(c)
    d, di, S, G, W, H, P, Q, V, L = (
        z[k] for k in "d di S G W H P Q V L".split())
    proj = 2 * d * (2 * di + 2 * G * S + H) + 2 * di * d
    conv = 2 * W * (di + 2 * G * S)
    ssd = H * (2 * Q * S + 2 * Q * P + 2 * S * P + 2 * S * P)
    return L * (proj + conv + ssd) + 2 * d * V


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward (3 x forward) per training token."""
    if c["family"] != "ssm":
        raise ValueError(f"no training count for family {c['family']!r}")
    return 3.0 * ssm_forward_flops_per_token(c)


def dense_matmul_params(c: dict) -> tuple:
    """(per-layer matrix parameters, head parameters) of a dense model."""
    z = dense_sizes(c)
    d, H, K, hd, f, V = (z[k] for k in "d H K hd f V".split())
    layer = d * (H * hd + 2 * K * hd) + H * hd * d + 3 * d * f
    return layer, d * V


def _attn_flops(c: dict, q_tokens: float, kv_tokens: float) -> float:
    z = dense_sizes(c)
    return z["L"] * 4.0 * q_tokens * kv_tokens * z["H"] * z["hd"]


def prefill_flops(c: dict, length: int) -> float:
    """One prompt of ``length`` true tokens: every layer over every token
    (causal attention: each token attends to itself and those before),
    the head at the last position only."""
    layer, head = dense_matmul_params(c)
    L = dense_sizes(c)["L"]
    return (2.0 * L * layer * length + 2.0 * head
            + _attn_flops(c, 1.0, 1.0) * length * (length + 1) / 2)


def decode_flops(c: dict, lengths) -> float:
    """One decode step for the active slots; ``lengths`` are the cache
    positions each attends to (its new token included)."""
    layer, head = dense_matmul_params(c)
    L = dense_sizes(c)["L"]
    return sum(2.0 * (L * layer + head) + _attn_flops(c, 1.0, n)
               for n in lengths)


def kv_bytes_per_position(c: dict) -> int:
    z = dense_sizes(c)
    width = 2 if c["dtype"] == "bfloat16" else 4
    return 2 * z["L"] * z["K"] * z["hd"] * width


def decode_bytes(c: dict, lengths) -> float:
    """One decode step: every matrix read once, each active slot's
    embedding row, and its K/V over its actual length (the positions
    before the new token read, the new one written)."""
    layer, head = dense_matmul_params(c)
    z = dense_sizes(c)
    width = 2 if c["dtype"] == "bfloat16" else 4
    weights = (z["L"] * layer + head) * width
    rows = len(lengths) * z["d"] * width
    kv = kv_bytes_per_position(c) * sum(lengths)
    return float(weights + rows + kv)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time: operations at peak, or bytes at the
    memory's bandwidth, whichever is longer."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
