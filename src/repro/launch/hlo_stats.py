"""Re-export shim: the HLO parse/accounting core moved to
``repro.analysis.footprint`` (the lanelint static-analysis subsystem
generalized it into the shared footprint layer, DESIGN.md §12).

Every name that ever lived here keeps working — benchmarks, the dryrun
reporter, the conformance grid and the structural-overlap tests all
import through this module; new code should import
``repro.analysis.footprint`` directly.
"""
from repro.analysis.footprint import (  # noqa: F401
    _COLL_KINDS,
    _DTYPE_BYTES,
    _RESULT_BYTES_OPS,
    _SKIP_BYTES_OPS,
    Computation,
    Instr,
    _ancestor_fn,
    _bytes_of,
    _called_comps,
    _carrier_comps,
    _collective,
    _dims,
    _elems_of,
    _independent,
    _instr_bytes,
    _operand_names,
    analyze,
    collective_compute_concurrency,
    collective_concurrency,
    collective_kind_counts,
    comm_footprint,
    group_info,
    parse_hlo,
    permute_edges,
    replica_groups,
)

__all__ = [
    "analyze", "collective_kind_counts", "collective_concurrency",
    "collective_compute_concurrency", "comm_footprint", "group_info",
    "parse_hlo", "replica_groups", "permute_edges",
]
