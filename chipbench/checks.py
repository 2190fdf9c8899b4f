"""The numbers that decide ``correct``, each compared with its limit.

Training (the first ``check_steps`` steps against the reference):
  loss_gap    largest relative gap of a step's loss
  grad_gap    worst leaf of the first gradient: |norm - ref norm| over the
              larger of that leaf's reference norm and the median leaf's
  change_gap  the same for the parameters' change over the steps, leaving
              out the leaves whose reference gradient is under a
              thousandth of the median leaf's (they move by round-off)

Serving (a sample of finished requests against the reference):
  logit_gap   widest gap by which a served token's reference logit lies
              below the reference's best at that position
"""
from __future__ import annotations

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone
NOUGHT = 1e-3


def config_matches(cfg, config: dict) -> None:
    """The program's registered configuration must have the sizes of the
    configuration file the reference reads; raises ValueError otherwise."""
    if config["family"] == "ssm":
        pairs = {"num_layers": config["n_layer"],
                 "d_model": config["d_model"],
                 "vocab_size": config["vocab_size"],
                 "ssm_state": config["d_state"],
                 "ssm_head_dim": config["headdim"],
                 "ssm_expand": config["expand"],
                 "ssm_groups": config["ngroups"],
                 "ssm_conv_width": config["d_conv"],
                 "ssm_chunk": config["chunk_size"],
                 "tie_embeddings": config["tie_embeddings"],
                 "norm_eps": config["rms_norm_eps"],
                 "dtype": config["dtype"]}
    else:
        pairs = {"num_layers": config["num_hidden_layers"],
                 "d_model": config["hidden_size"],
                 "num_heads": config["num_attention_heads"],
                 "num_kv_heads": config["num_key_value_heads"],
                 "d_ff": config["intermediate_size"],
                 "vocab_size": config["vocab_size"],
                 "rope_theta": config["rope_theta"],
                 "sliding_window": config["sliding_window"],
                 "tie_embeddings": config["tie_word_embeddings"],
                 "norm_eps": config["rms_norm_eps"],
                 "dtype": config["dtype"]}
        if cfg.hd() != config["head_dim"]:
            raise ValueError(f"{cfg.name}: head_dim {cfg.hd()} != "
                             f"{config['head_dim']}")
    bad = {k: (getattr(cfg, k), v) for k, v in pairs.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"registered {cfg.name} differs from its "
                         f"configuration file: {bad}")


def leaf_gap(prog, ref) -> np.ndarray:
    """Per-leaf |prog - ref| over max(ref, median ref)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = max(float(np.median(ref)), 1e-30)
    return np.abs(prog - ref) / np.maximum(ref, floor)


def train_numbers(losses, grad1, change, ref) -> dict:
    lp, lr = np.asarray(losses, np.float64), np.asarray(ref["losses"],
                                                         np.float64)
    moved = ref["grad1"] >= NOUGHT * np.median(ref["grad1"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": float(np.max(leaf_gap(grad1, ref["grad1"]))),
        "change_gap": float(np.max(leaf_gap(change, ref["change"])[moved])),
    }


def worst_leaves(got: dict, ref: dict, names: list) -> dict:
    """The leaf that sets each per-leaf number, by name."""
    moved = ref["grad1"] >= NOUGHT * np.median(ref["grad1"])
    change = np.where(moved, leaf_gap(got["change"], ref["change"]), -1.0)
    return {"grad_gap": names[int(np.argmax(leaf_gap(got["grad1"],
                                                     ref["grad1"])))],
            "change_gap": names[int(np.argmax(change))]}


def with_limits(numbers: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def served_gap(ref_logits: np.ndarray, served) -> float:
    """Widest gap of the served tokens below the reference's best; row j
    of ``ref_logits`` is the position that predicted ``served[j]``."""
    ref_logits = np.asarray(ref_logits, np.float64)
    best = ref_logits.max(axis=-1)
    got = ref_logits[np.arange(len(served)), np.asarray(served)]
    return float(np.max(best - got))
