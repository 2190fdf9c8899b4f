"""Parameter-layout descriptors for the registered train-step strategies.

A gradsync strategy is more than a collective schedule: the ZeRO flavors
change where the MASTER parameters and optimizer moments live (fully
replicated tree vs node-sharded flat vector vs the bucket-major 1/p
(L, B, p, s) layer stack of DESIGN.md §5).  Everything outside the jitted
step — the training driver's state init, the shard_map in/out specs, and
above all the checkpoint store — must agree with the step on that layout,
and before this module each consumer hard-coded its own copy of the
mapping.

Here the mapping is one more registry: every ``train_step`` registration
declares its layout kind via :func:`register_param_layout`, and
:meth:`LaneComm.param_layout <repro.comm.LaneComm.param_layout>` answers
the question "what master layout does this strategy expect on THIS
topology" — including the single-batch-axis degradation (an empty node
level collapses ZeRO-1 to the replicated native step, mirroring the step
builders in :mod:`repro.launch.steps`).

Kinds:

  replicated  params and optimizer state are ordinary pytrees, identical
              on every chip (native / lane / lane_pipelined / lane_int8 /
              auto).
  zero1       params replicated; optimizer moments are ONE flat fp32
              vector sharded over the node axes in the bucket-major
              ``gradsync.zero1_param_shard`` layout.
  zero3       the family's scanned layer stack AND the embeddings/
              final-norm "extras" pseudo-layer (params AND moments) live
              in the bucket-major (L, B, p, s) master layouts of
              ``repro.models.blockstack.shard_stack``; only the family
              spec's replicated_keys (the hybrid's weight-shared blocks
              with their per-application adapters and projections) stay
              replicated.

The concrete checkpoint canonicalization for each kind lives in
:mod:`repro.checkpoint.layouts`.
"""
from __future__ import annotations

PARAM_LAYOUT_KINDS = ("replicated", "zero1", "zero3")

_TABLE: dict[str, str] = {}


def register_param_layout(strategy: str, kind: str) -> None:
    """Declare the master-parameter layout of one train-step strategy.

    Called next to the strategy's ``@register_impl("train_step", ...)``
    registration; re-registering with a DIFFERENT kind raises (the layout
    is a contract every checkpoint ever written under the strategy
    depends on).
    """
    if kind not in PARAM_LAYOUT_KINDS:
        raise ValueError(
            f"unknown param layout kind {kind!r}; have {PARAM_LAYOUT_KINDS}")
    old = _TABLE.get(strategy)
    if old is not None and old != kind:
        raise ValueError(
            f"train-step strategy {strategy!r} already registered with "
            f"param layout {old!r}; cannot re-register as {kind!r}")
    _TABLE[strategy] = kind


def param_layout_kind(strategy: str) -> str:
    """The registered layout kind for ``strategy`` (topology-blind —
    use :meth:`LaneComm.param_layout` for the degradation-aware answer)."""
    if strategy not in _TABLE:
        raise ValueError(
            f"no param layout registered for train-step strategy "
            f"{strategy!r}; registered: {tuple(_TABLE)}")
    return _TABLE[strategy]
