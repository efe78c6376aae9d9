"""Typed option surfaces for the LayoutService facade.

Seven PRs of keyword accretion left ``LayoutService.ingest(observe=,
monitor=, fused=)`` / ``ingest_sharded(..., executor=)`` /
``auto_rebuilder(workload=, tracker=, config=)`` as an untyped kwarg
sprawl — and the replica dimension would have multiplied it.  These
dataclasses are the consolidated spellings, now covering the parallelism
axis too, so ONE entry point ingests everything:

    svc.ingest(batches)                                   # streaming
    svc.ingest(records, IngestOptions(shards=4))          # process-parallel
    svc.ingest(records, IngestOptions(shards=4,
                                      coordinator=fleet)) # fleet-folded
    svc.auto_rebuilder(RebuildPolicy(workload="auto", tracker=t))

The loose ``observe=``/``monitor=``/``fused=``/``executor=`` kwargs had
their one-release deprecation window (with warnings naming the
replacement); the window is closed and they now raise ``TypeError``.
The ``ingest_sharded(records, n_shards)`` method is the current
one-release shim: it forwards to ``ingest`` with a DeprecationWarning.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

#: kwargs that belong to IngestOptions; loose spellings are rejected.
_INGEST_OPTION_KEYS = (
    "observe", "monitor", "fused", "executor", "shards", "batch",
    "coordinator",
)


@dataclasses.dataclass(frozen=True)
class IngestOptions:
    """How one ingest run observes, monitors, and parallelizes.

    observe      Workload | WorkloadTensors | ObservationProbe — Eq. 1
                 per-batch skip accounting against a standing workload.
    monitor      an :class:`~repro.service.drift.AutoRebuilder`: batches
                 tee into its reservoir and observations drive its drift
                 policy (may fire a background rebuild mid-stream).
    fused        single-pass route+tighten kernels (default) vs the
                 two-pass route-then-tighten path.
    executor     sharded runs: ``None`` picks ``"process"`` (resident
                 spawn workers) for ``shards >= 2`` off the TPU and
                 ``"thread"`` otherwise; on a TPU host a process
                 executor raises (one process per chip); ``"thread"``
                 with multiple shards off the TPU carries a
                 documented PerformanceWarning (GIL-bound, measured
                 0.44x); any ``concurrent.futures`` Executor instance is
                 used as-is.
    shards       None/1 streams single-stream; k >= 2 splits the record
                 array across k ShardIngestors and folds their states
                 associatively (requires an ndarray, not a batch
                 iterable).
    batch        micro-batch rows when ``ingest`` is handed a record
                 array (sharded or not).
    coordinator  a :class:`~repro.coordinator.FleetCoordinator`: the run
                 routes and aggregates but does NOT publish locally —
                 the merged ShardState is submitted to the coordinator,
                 which folds partials fleet-wide and owns every publish.
    """

    observe: object = None
    monitor: object = None
    fused: bool = True
    executor: object = None
    shards: Optional[int] = None
    batch: int = 2048
    coordinator: object = None

    def __post_init__(self):
        if self.shards is not None and self.shards < 1:
            raise ValueError("shards must be >= 1")
        if self.batch < 1:
            raise ValueError("batch must be >= 1")


@dataclasses.dataclass(frozen=True)
class RebuildPolicy:
    """When and how the service rebuilds itself.

    workload     a declared standing Workload, or ``"auto"`` to score
                 drift (and rebuild) against the tracker-inferred live
                 mix.
    tracker      the WorkloadTracker the serving path records into
                 (``workload="auto"``; omitted, one is created).
    drift        :class:`~repro.service.drift.DriftConfig` trigger
                 policy (threshold + hysteresis + cooldown).
    replicas     k > 1 makes triggered rebuilds deploy a k-replica
                 set via :meth:`LayoutService.rebuild_replicas`
                 (cheapest-replica routing); 1 keeps today's
                 single-tree rebuild.
    lam          uniform-prior blend weight for replica clustering
                 (see ``repro.service.replica``).
    reservoir_capacity  recent-record reservoir size for rebuilds.
    executor     ``None`` (private worker thread), ``"sync"``
                 (rebuild inline — deterministic tests/benchmarks),
                 or any Executor.
    rebuild_kw   extra kwargs forwarded to ``service.rebuild`` /
                 ``service.rebuild_replicas`` (e.g. ``swap=``,
                 ``strategy=``, ``min_block=``).
    """

    workload: object = "auto"
    tracker: object = None
    drift: object = None  # DriftConfig | None
    replicas: int = 1
    lam: float = 0.25
    reservoir_capacity: int = 65536
    executor: object = None
    rebuild_kw: Optional[dict] = None

    def __post_init__(self):
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")
        if not 0.0 <= self.lam <= 1.0:
            raise ValueError("lam must be in [0, 1]")


def resolve_ingest_options(
    options: Optional[IngestOptions],
    kw: dict,
    method: str,
) -> IngestOptions:
    """Reject retired loose option kwargs; return the effective options.

    The one-release shim that lifted loose ``observe=``/``monitor=``/
    ``fused=``/``executor=`` kwargs into IngestOptions (with a
    DeprecationWarning each) is retired: any option-surface kwarg in
    ``kw`` now raises ``TypeError`` naming the typed spelling.  The
    remaining ``kw`` passes through to the engine layer untouched
    (``tighten=``, ``buffers=``, ``backend=`` ...).
    """
    loose = sorted(k for k in _INGEST_OPTION_KEYS if k in kw)
    if loose:
        names = ", ".join(f"{k}=" for k in loose)
        raise TypeError(
            f"{method}() no longer accepts the loose kwarg(s) {names} "
            f"(the deprecation window closed); pass "
            f"options=IngestOptions({names}...)"
        )
    return options if options is not None else IngestOptions()


__all__ = ["IngestOptions", "RebuildPolicy", "resolve_ingest_options"]
