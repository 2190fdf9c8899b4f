"""CommConfig — the typed communication config behind one LaneComm.

Absorbs the loose per-field knobs that used to ride on ``RunConfig``
(``gradsync`` strategy string, ``gradsync_buckets``, ``fsdp_prefetch``)
behind one frozen dataclass, so a LaneComm carries its whole tuning
surface and a new knob is one field here instead of a new int threaded
through every call site.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids an import cycle
    from repro.configs.base import RunConfig
    from repro.tuning.table import Tuner

_COMPRESSIONS = ("none", "int8")


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Tuning surface of one :class:`~repro.comm.LaneComm`.

    strategy: default strategy for ``grad_sync`` (and any collective for
        which that name is registered).  ``"auto"`` ranks the registered
        auto-eligible implementations with the cost model per call.
    buckets: gradient-sync bucket count K; 0 = cost-model auto (the §5
        latency/bandwidth crossover, ``core.costmodel.optimal_num_buckets``).
    prefetch_blocks: ZeRO-3 per-layer weight-gather pipeline blocks B;
        0 = cost-model auto, >0 = override, -1 = BLOCKING gather (the
        negative control: ``prefetch_allgather`` dispatches to the
        ``"blocking"`` strategy).
    compression: DCN payload compression ("none" | "int8").  Descriptive
        — ``lane_int8`` is never auto-selected (lossy); this records that
        the owner opted in.
    record_selections: append a Selection record per auto dispatch (read
        by the HLO structural checkers / benchmarks).
    tuner: measured-cost hook (``repro.tuning.table.Tuner``).  When set,
        ``LaneComm.select`` asks it for a MEASURED cost per candidate
        strategy and ranks measured cells ahead of closed-form-modelled
        ones (unmeasured cells fall back to the §3/§5 model — the
        measure-once-then-commit contract, DESIGN.md §11).  None (the
        default) keeps dispatch purely on the closed-form model.
    """

    strategy: str = "auto"
    buckets: int = 0
    prefetch_blocks: int = 0
    compression: str = "none"
    record_selections: bool = True
    tuner: Optional[Tuner] = None

    def __post_init__(self):
        if self.compression not in _COMPRESSIONS:
            raise ValueError(
                f"unknown compression {self.compression!r}; "
                f"have {_COMPRESSIONS}")
        if self.strategy != "auto":
            # catch typos at construction: a default strategy must name
            # SOME registration (per-collective resolution still falls
            # back to auto where the name isn't registered — deliberate)
            from .registry import has_impl, registered_collectives
            if not any(has_impl(c, self.strategy)
                       for c in registered_collectives()):
                raise ValueError(
                    f"unknown strategy {self.strategy!r}: not registered "
                    f"for any collective (inspect the tables via "
                    f"repro.comm.strategies_for)")

    @classmethod
    def from_run(cls, run: "RunConfig") -> "CommConfig":
        """Bridge from the legacy RunConfig knobs."""
        return cls(
            strategy=run.gradsync,
            buckets=run.gradsync_buckets,
            prefetch_blocks=run.fsdp_prefetch,
            compression="int8" if run.gradsync == "lane_int8" else "none",
        )
