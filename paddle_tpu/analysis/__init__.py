"""tpu-lint: static SPMD program verification over the fluid IR + HLO.

Every failure class the runtime guards against dynamically — a
rank-divergent collective schedule that hangs the pod mid-run, a
donated buffer read after aliasing, a host sync serializing the async
step pipeline, a sharding plan whose padding leaks into optimizer state
— is detectable STATICALLY from the Program IR (and, for collectives,
the lowered StableHLO), before a single chip cycle is spent. Chip time
is budgeted; these checkers turn "hangs 40 minutes into a chip run"
into "fails in CI in 4 seconds".

Six checkers (see README.md in this directory for the full catalog):

1. ``collective-divergence`` — per-rank programs (and branch regions)
   must emit identical collective schedules (collectives.py).
2. ``donation-safety`` — no op holds a feed/state buffer past its
   donated in-place rebind (donation.py).
3. ``host-sync`` — fetch/RPC/host-callback ops inside while/scan
   bodies defeat the async pipeline (host_sync.py).
4. ``zero1-invariants`` — shard-plan padding zeroing, bucket dtype
   homogeneity, checkpoint save/restore layout (sharding.py).
5. ``zero2-lifetimes`` — no op reads a FULL gradient after its bucket
   reduce-scattered; buckets flush whole, fetches of scattered grads
   flagged (sharding.py).
6. ``dtype-contract`` — declared vs computed out dtype/shape, silent
   fp64 promotions, redundant AMP cast round-trips, plus quantized
   programs: slim/PTQ fake-quant ops missing their calibrated scale
   input = ERROR (contracts.py).

Surfaces: ``tools/tpu_lint.py`` (CLI, JSON artifact, --fail-on),
``FLAGS_tpu_static_checks={off,warn,error}`` (Executor compile-time
hook), and ``bench.py``'s ``"static_checks"`` summary block.

Beyond the per-program IR checkers there is a PROTOCOL tier
(protocol.py + proto_models.py): an explicit-state interleaving
checker that drives the REAL host-protocol implementations — RPC
envelope retry/dedupe, PS exactly-once apply across kill/restart, the
elastic preemption seam, serving drain->adopt and the paged-KV page
ledger — through every reachable message/crash/preemption
interleaving up to a schedule budget, checking exactly-once, seam
agreement, drain conservation, page conservation and deadlock-freedom
at every state. Violations surface as ``Finding``s with compact
REPLAYABLE traces (``tools/tpu_lint.py --protocol``).
"""
from __future__ import annotations

from typing import Iterable, List, Optional

from .findings import (Finding, SEVERITIES, format_finding,  # noqa: F401
                       sort_findings, summarize, worst_severity)
from .collectives import (IR_COLLECTIVE_OPS,  # noqa: F401
                          check_branch_uniformity,
                          check_collective_divergence,
                          check_hierarchical_groups,
                          check_hlo_divergence, collective_schedule,
                          hlo_collective_schedule,
                          runtime_schedule_key)
from .donation import (check_donation_safety,  # noqa: F401
                       cross_check_donation_report)
from .host_sync import check_host_sync  # noqa: F401
from .sharding import (check_shard_plan,  # noqa: F401
                       check_sparse_update, check_zero2_lifetimes)
from .contracts import (check_dtype_shape_contracts,  # noqa: F401
                        check_quantization_contracts)
from .protocol import (ExploreResult, ProtocolModel,  # noqa: F401
                       explore, format_trace, parse_trace, replay,
                       run_protocol_checks)

__all__ = [
    "Finding", "SEVERITIES", "CHECKERS", "format_finding",
    "sort_findings", "summarize", "worst_severity",
    "IR_COLLECTIVE_OPS", "collective_schedule",
    "check_branch_uniformity", "check_collective_divergence",
    "hlo_collective_schedule", "check_hlo_divergence",
    "check_hierarchical_groups", "runtime_schedule_key",
    "check_donation_safety", "cross_check_donation_report",
    "check_host_sync", "check_shard_plan", "check_sparse_update",
    "check_zero2_lifetimes", "check_dtype_shape_contracts",
    "check_quantization_contracts", "run_static_checks",
    "ProtocolModel", "ExploreResult", "explore", "replay",
    "format_trace", "parse_trace", "run_protocol_checks",
]

#: checker registry: name -> "does it run in the single-program pass"
CHECKERS = ("collective-divergence", "donation-safety", "host-sync",
            "zero1-invariants", "zero2-lifetimes", "sparse-update",
            "dtype-contract")


def run_static_checks(program, feed_names=None, fetch_names=None,
                      checkers: Optional[Iterable[str]] = None,
                      rank_programs=None, rank_labels=None,
                      donation_report=None) -> List[Finding]:
    """Run the selected checkers over one program (plus, when
    ``rank_programs`` is given, the cross-rank collective-divergence
    pass over the whole set). Returns severity-sorted findings.

    ``donation_report``: an ``Executor.donation_report`` dict of the
    same program, reconciled against the static donation verdict.
    """
    sel = set(checkers) if checkers is not None else set(CHECKERS)
    unknown = sel - set(CHECKERS)
    if unknown:
        raise ValueError("unknown checker(s) %s; have %s"
                         % (sorted(unknown), list(CHECKERS)))
    findings: List[Finding] = []
    if "collective-divergence" in sel:
        findings += check_branch_uniformity(program)
        if rank_programs:
            progs = list(rank_programs)
            labels = list(rank_labels) if rank_labels else None
            if program not in progs:
                progs = [program] + progs
                if labels is not None and len(labels) == len(progs) - 1:
                    # the caller labeled only rank_programs; label the
                    # prepended reference program too so a divergence
                    # at the last rank doesn't index past the list
                    labels = ["main"] + labels
            findings += check_collective_divergence(progs, labels=labels)
    if "donation-safety" in sel:
        dfs = check_donation_safety(program, feed_names=feed_names,
                                    fetch_names=fetch_names)
        findings += dfs
        findings += cross_check_donation_report(dfs, donation_report)
    if "host-sync" in sel:
        findings += check_host_sync(program)
    if "zero1-invariants" in sel:
        findings += check_shard_plan(program)
    if "zero2-lifetimes" in sel:
        findings += check_zero2_lifetimes(program,
                                          fetch_names=fetch_names)
    if "sparse-update" in sel:
        findings += check_sparse_update(program,
                                        fetch_names=fetch_names)
    if "dtype-contract" in sel:
        findings += check_dtype_shape_contracts(program)
        # quantized programs: PTQ calibrated-scale presence (ERROR
        # severity — wrong math, not drifted declarations)
        findings += check_quantization_contracts(program)
    return sort_findings(findings)
