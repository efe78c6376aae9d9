"""LayoutEngine: the single serving interface over a frozen qd-tree.

Consolidates the routing backends (core/routing.py), the Pallas operand
packing (kernels/ops.py), query↔block intersection (core/rewards.py) and
streaming ingestion into block buffers (data/blocks.py) behind one object:

    eng = LayoutEngine(frozen_tree, backend="jax")
    bids = eng.route(records)                   # any registered backend
    hits = eng.query_hits(workload)             # (n_leaves, n_queries) bool
    lists = eng.route_queries(workload)         # per-query BID IN (...) lists
    stats = eng.skip_stats(records, workload)   # paper Eq. 1 metrics
    report = eng.ingest(batch_iter)             # online micro-batch ingestion

All backends are bit-identical; compiled plans (jit/Pallas executables plus
their packed operands) are cached per power-of-two padding bucket so online
ingestion of varying batch sizes never retraces (``eng.stats()`` exposes the
plan-cache and trace counters).
"""

from __future__ import annotations

# qdlint: deterministic-module

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Iterable, Iterator, Optional

import numpy as np

from repro import spans
from repro.core import query as qry
from repro.core.qdtree import FrozenQdTree, IncrementalTightener
from repro.engine import backends as be
from repro.engine import plan as planlib
from repro.engine.plan import PlanCache


@dataclasses.dataclass(frozen=True)
class WindowStat:
    """Associative Eq. 1 accounting partial over one or more batches.

    ``scanned_tuples`` is the paper's Σ_q Σ_{P ∩ q} |P| restricted to the
    observed records; ``capacity`` the matching denominator
    Σ_batches (n_records · n_queries).  All fields are exact int64-range
    Python ints, so :meth:`merge` (elementwise sum) is associative *and*
    commutative bit-identically — shard partials fold in any order to the
    same totals as the single-stream per-batch sequence.
    """

    scanned_tuples: int = 0
    capacity: int = 0  # Σ n_records * n_queries over observed batches
    n_records: int = 0

    @property
    def scanned_fraction(self) -> float:
        """Eq. 1 fraction of tuples the standing workload would scan."""
        return self.scanned_tuples / self.capacity if self.capacity else 0.0

    def merge(self, other: "WindowStat") -> "WindowStat":
        return WindowStat(
            scanned_tuples=self.scanned_tuples + other.scanned_tuples,
            capacity=self.capacity + other.capacity,
            n_records=self.n_records + other.n_records,
        )

    # -- serialization (ShardState npz shipping) -----------------------------
    def to_array(self) -> np.ndarray:
        return np.asarray(
            [self.scanned_tuples, self.capacity, self.n_records], np.int64
        )

    @staticmethod
    def from_array(a: np.ndarray) -> "WindowStat":
        return WindowStat(*(int(x) for x in a))


@dataclasses.dataclass(frozen=True)
class ObservationProbe:
    """Per-leaf hit accounting against one standing workload.

    ``per_leaf[b]`` is the number of queries whose ``BID IN (...)`` list
    contains block ``b`` — ``query_hits(workload).sum(axis=1)`` computed
    once through the compiled plan.  Per-batch accounting is then a pure
    numpy gather+sum (``observe``): O(m) per batch, no backend dispatch,
    so ingest-time skip-rate monitoring never retraces a warm plan.
    """

    per_leaf: np.ndarray  # (n_leaves,) int64 queries scanning each block
    n_queries: int

    def observe(self, bids: np.ndarray) -> WindowStat:
        """Eq. 1 partial for one routed batch."""
        m = int(bids.shape[0])
        return WindowStat(
            scanned_tuples=int(self.per_leaf[bids].sum()),
            capacity=m * self.n_queries,
            n_records=m,
        )


@dataclasses.dataclass
class IngestReport:
    """Summary of one streaming-ingestion run."""

    n_batches: int
    n_records: int
    block_sizes: np.ndarray  # (n_leaves,) records routed per block
    wall_s: float
    backend: str
    plan_cache: dict  # hits/misses/size snapshot
    traces: dict  # trace-counter deltas during the run
    observation: Optional[WindowStat] = None  # set iff ``observe`` was given
    fused: bool = False  # True when the single-pass route+tighten path ran

    @property
    def records_per_s(self) -> float:
        return self.n_records / self.wall_s if self.wall_s else 0.0


class WorkloadTensorCache(collections.OrderedDict):
    """LRU of tensorized workloads with its own lock.

    Concurrent query threads (and generations sharing one cache across a
    hot swap) interleave get / move_to_end / popitem — the lock keeps the
    multi-step LRU update atomic.  Tensorization itself runs outside it.
    """

    def __init__(self):
        super().__init__()
        self.lock = threading.Lock()


class LayoutEngine:
    """Backend-dispatched routing/query API with a compiled-plan cache."""

    WT_CACHE_CAP = 16  # live workload-tensor entries kept per engine

    def __init__(
        self,
        tree: FrozenQdTree,
        backend: str = "jax",
        interpret: Optional[bool] = None,
        plan_cache: Optional[PlanCache] = None,
        wt_cache: Optional[WorkloadTensorCache] = None,
    ):
        be.get_backend(backend)  # validate eagerly
        self.tree = tree
        self.backend = backend
        self.interpret = interpret
        self.plans = plan_cache if plan_cache is not None else PlanCache()
        # LRU of tensorized workloads, keyed by (cut-table content
        # signature, workload id).  Values keep a strong reference to the
        # workload itself: while an entry lives its id() cannot be reused
        # by CPython, so two distinct workloads can never alias the same
        # key (the identity check in _tensorize is belt and braces).
        # LayoutService passes one shared dict to every generation's
        # engine: tensorization depends only on schema + cuts, so a hot
        # swap to a tree built from an equal cut table reuses standing
        # workload tensors instead of re-tensorizing them.
        self._wt_cache: WorkloadTensorCache = (
            wt_cache if wt_cache is not None else WorkloadTensorCache()
        )

    # -- dispatch -----------------------------------------------------------
    def _backend(self, override: Optional[str]) -> be.Backend:
        return be.get_backend(override or self.backend)

    def _opts(self) -> dict:
        return {} if self.interpret is None else {"interpret": self.interpret}

    # -- routing ------------------------------------------------------------
    def route(  # qdlint: hot-path
        self, records: np.ndarray, backend: Optional[str] = None, **opts
    ) -> np.ndarray:
        """Record batch → (m,) int32 BIDs (paper Sec 3.1)."""
        if records.shape[0] == 0:
            return np.zeros(0, np.int32)
        kw = {**self._opts(), **opts}
        return self._backend(backend).route(
            self.tree, self.plans, records, **kw
        )

    # -- query processing ---------------------------------------------------
    def _tensorize(self, workload: qry.Workload) -> qry.WorkloadTensors:
        key = (planlib.cuts_signature(self.tree.cuts), id(workload))
        with self._wt_cache.lock:
            hit = self._wt_cache.get(key)
            if hit is not None and hit[0] is workload:
                self._wt_cache.move_to_end(key)
                return hit[1]
        wt = workload.tensorize(self.tree.cuts)  # expensive: outside lock
        with self._wt_cache.lock:
            self._wt_cache[key] = (workload, wt)
            self._wt_cache.move_to_end(key)
            while len(self._wt_cache) > self.WT_CACHE_CAP:
                self._wt_cache.popitem(last=False)  # evict LRU entry
        return wt

    def query_hits(  # qdlint: hot-path
        self,
        workload: qry.Workload | qry.WorkloadTensors,
        backend: Optional[str] = None,
        **opts,
    ) -> np.ndarray:
        """(n_leaves, n_queries) bool — blocks each query must scan."""
        wt = (
            workload
            if isinstance(workload, qry.WorkloadTensors)
            else self._tensorize(workload)
        )
        kw = {**self._opts(), **opts}
        return self._backend(backend).query_hits(
            self.tree, self.plans, wt, **kw
        )

    def route_queries(  # qdlint: hot-path
        self,
        workload: qry.Workload | qry.WorkloadTensors,
        backend: Optional[str] = None,
        track=None,  # service.tracker.WorkloadTracker | None
        **opts,
    ) -> list[np.ndarray]:
        """Per-query BID IN (...) lists for a whole workload (Sec 3.3).

        The batched counterpart of :meth:`route_query` — one tensorization
        and one ``query_hits`` dispatch serve every query, so the jitted
        backends amortize compilation across the workload (the p50 latency
        fix flagged in ROADMAP; see ``benchmarks/query_routing.py``).

        ``track`` is the workload auto-detection observation hook: each
        served query's canonical predicate signature is recorded into the
        given :class:`~repro.service.tracker.WorkloadTracker` (pure host
        numpy — no backend dispatch, no plan-cache traffic, so tracking a
        warm serving path never retraces).
        """
        wt = (
            workload
            if isinstance(workload, qry.WorkloadTensors)
            else self._tensorize(workload)
        )
        if track is not None:
            track.record(workload, cuts=self.tree.cuts)
        hits = self.query_hits(wt, backend=backend, **opts)
        return [
            np.nonzero(hits[:, q])[0].astype(np.int32)
            for q in range(wt.n_queries)
        ]

    def route_query(self, query: qry.Query, track=None) -> np.ndarray:
        """BID IN (...) list for one query — 1-query ``route_queries``.

        Stays on the numpy backend (a single query never amortizes a jit
        dispatch) and tensorizes directly so one-shot queries don't churn
        the workload-tensor LRU.  ``track`` records the query into a
        :class:`~repro.service.tracker.WorkloadTracker` exactly as the
        batched path does.
        """
        wl = qry.Workload(self.tree.schema, (query,))
        if track is not None:
            track.record(wl, cuts=self.tree.cuts)
        return self.route_queries(
            wl.tensorize(self.tree.cuts), backend="numpy"
        )[0]

    def skip_stats(
        self,
        records: np.ndarray,
        workload: qry.Workload,
        tighten: bool = True,
        backend: Optional[str] = None,
    ):
        """Route + (optionally) tighten + score: paper Eq. 1 SkipStats."""
        from repro.core import rewards

        bids = self.route(records, backend=backend)
        if tighten:
            self.tree.tighten(records, bids)
        sizes = np.bincount(bids, minlength=self.tree.n_leaves).astype(
            np.int64
        )
        hits = self.query_hits(workload, backend=backend)
        scanned = int((hits * sizes[:, None]).sum())
        total = records.shape[0] * len(workload)
        return rewards.SkipStats(
            n_records=records.shape[0],
            n_queries=len(workload),
            n_blocks=self.tree.n_leaves,
            scanned_tuples=scanned,
            skipped_tuples=total - scanned,
            block_sizes=sizes,
            query_hits=hits,
        )

    # -- streaming ingestion -------------------------------------------------
    def fused_step(  # qdlint: hot-path
        self,
        records: np.ndarray,
        backend: Optional[str] = None,
        fold: Optional[contextlib.ExitStack] = None,
        **opts,
    ):
        """One single-pass route + tighten step (no tree mutation).

        Returns ``(bids int32 (m,), TightenPartial)`` — bit-identical to
        :meth:`route` followed by ``IncrementalTightener.update`` over the
        same records, but each record is touched once (the fused kernels;
        see ``kernels/fused_ingest.py``).  The caller folds the partial
        into a tightener (``merge``) or a shard reduction.

        The backend stages the batch and pulls the step's outputs (the
        ``engine.stage`` and ``engine.device_wait`` spans); turning them
        into the partial opens ``engine.fold``.  Given ``fold``, that span
        is entered on the caller's stack, so the caller's own share of
        the fold lands in the same span when it closes the stack; without
        it, the span closes on return.
        """
        if records.shape[0] == 0:
            return (
                np.zeros(0, np.int32),
                IncrementalTightener(self.tree).as_partial(),
            )
        kw = {**self._opts(), **opts}
        step = self._backend(backend)
        bids, outputs = step.fused_outputs(self.tree, self.plans, records,
                                           **kw)
        with contextlib.ExitStack() as own:
            (own if fold is None else fold).enter_context(
                spans.span(spans.ENGINE_FOLD)
            )
            return bids, step.fold_outputs(self.tree, outputs)

    def warm_ingest(
        self,
        sizes: Iterable[int],
        backend: Optional[str] = None,
        **opts,
    ) -> None:
        """Compile fused-ingest plans for these batch sizes.

        Routes zero-filled dummy batches through :meth:`fused_step` so the
        per-bucket compilations land in the plan cache before real data
        arrives; the tree itself is never mutated (the partials are
        discarded).  Callers that also serve queries should warm those
        separately via :meth:`query_hits`.
        """
        d = self.tree.leaf_lo.shape[1]
        for s in sorted({int(s) for s in sizes if int(s) > 0}):
            self.fused_step(np.zeros((s, d), np.int32), backend=backend,
                            **opts)

    def observation_probe(
        self,
        workload: "qry.Workload | qry.WorkloadTensors | ObservationProbe",
        backend: Optional[str] = None,
    ) -> ObservationProbe:
        """Per-leaf hit counts for ``workload`` against the current layout.

        One ``query_hits`` through the compiled plan (warm: zero retraces),
        reduced to ``(n_leaves,) int64``.  Already-built probes pass
        through, so shard fan-outs can compute once and replicate.
        """
        if isinstance(workload, ObservationProbe):
            return workload
        hits = self.query_hits(workload, backend=backend)
        return ObservationProbe(
            per_leaf=hits.sum(axis=1).astype(np.int64),
            n_queries=int(hits.shape[1]),
        )

    def ingest(
        self,
        batches: Iterable[np.ndarray] | Iterator[np.ndarray],
        tighten: bool = True,
        buffers=None,  # data.blocks.BlockBuffers | None
        backend: Optional[str] = None,
        observe=None,  # Workload | WorkloadTensors | ObservationProbe | None
        on_observation=None,  # Callable[[WindowStat], None] | None
        fused: bool = True,
    ) -> IngestReport:
        """Route arriving micro-batches and fold them into the layout.

        Per batch: route → append to ``buffers`` (if given) → incrementally
        min-max-tighten leaf descriptions.  The incremental tightener is
        exactly equivalent to one-shot ``FrozenQdTree.tighten`` over the
        concatenation of all batches (min/max/any are associative).

        With ``observe`` set (a standing workload or a pre-built
        :class:`ObservationProbe`), every routed batch is additionally
        scored against the workload's per-leaf hit counts — the paper's
        Eq. 1 restricted to that batch — and the resulting
        :class:`WindowStat` is passed to ``on_observation`` (the seam a
        drift monitor plugs into; see ``repro.service.drift``).  The run's
        aggregate lands in ``IngestReport.observation``.  The probe is
        built once per call from the layout as of the start of the run, so
        the accounting itself is a pure numpy gather — no retraces.

        ``fused=True`` (the default) takes the single-pass route+tighten
        path — :meth:`fused_step` per batch, partials folded into the
        tightener via ``merge`` — which is bit-identical to the legacy
        two-pass loop but touches each record once.  ``fused=False``
        restores the two-pass path (route, then host-side tighten).

        The call and each phase of a batch are profiler spans
        (``repro.spans``: ``engine.ingest``, ``engine.stage``,
        ``engine.device_wait``, ``engine.fold``, ``engine.publish``).
        """
        traces0 = planlib.trace_counts()
        probe = (
            self.observation_probe(observe, backend=backend)
            if observe is not None
            else None
        )
        observed = WindowStat() if probe is not None else None
        tightener = IncrementalTightener(self.tree) if tighten else None
        # the tightener already keeps per-leaf counts; only maintain a
        # separate accumulator when there is no tightener to read back
        sizes = None if tighten else np.zeros(self.tree.n_leaves, np.int64)
        use_fused = fused and tightener is not None
        # nothing downstream reads per-row block ids when there is no
        # spill buffer and no observation probe: skip their device→host
        # transfer per batch (the dominant host sync of the warm loop)
        need_bids = buffers is not None or probe is not None
        n_batches = n_records = 0
        t0 = time.perf_counter()
        with spans.span(spans.ENGINE_INGEST):
            for batch in batches:
                if batch.shape[0] == 0:
                    continue
                # one engine.fold span per batch: from the step's outputs
                # to the partial (inside fused_step) through the merge
                with contextlib.ExitStack() as fold:
                    if use_fused:
                        bids, part = self.fused_step(
                            batch, backend=backend, fold=fold,
                            return_bids=need_bids,
                        )
                    else:
                        bids = self.route(batch, backend=backend)
                        fold.enter_context(spans.span(spans.ENGINE_FOLD))
                    if buffers is not None:
                        buffers.append(batch, bids)
                    if use_fused:
                        tightener.merge(part)
                    elif tightener is not None:
                        tightener.update(batch, bids)
                    else:
                        sizes += np.bincount(bids, minlength=sizes.shape[0])
                    if probe is not None:
                        stat = probe.observe(bids)
                        observed = observed.merge(stat)
                        if on_observation is not None:
                            on_observation(stat)
                n_batches += 1
                n_records += batch.shape[0]
            if tightener is not None:
                with spans.span(spans.ENGINE_PUBLISH):
                    tightener.apply()
                    sizes = tightener.counts.copy()
        wall = time.perf_counter() - t0
        delta = planlib.trace_delta(traces0, planlib.trace_counts())
        return IngestReport(
            n_batches=n_batches,
            n_records=n_records,
            block_sizes=sizes,
            wall_s=wall,
            backend=backend or self.backend,
            plan_cache=self.plans.stats(),
            traces=delta,
            observation=observed,
            fused=use_fused,
        )

    # -- introspection -------------------------------------------------------
    def stats(self) -> dict:
        return {
            "backend": self.backend,
            "plan_cache": self.plans.stats(),
            "traces": planlib.trace_counts(),
        }


def engine_for(
    tree: FrozenQdTree, backend: str = "jax", **kw
) -> LayoutEngine:
    """The tree's attached engine (created on first use).

    Attaching keeps the plan cache alive across the legacy free-function
    callsites (``routing.route``, ``BlockStore.create``, benchmarks) without
    threading an engine object through every signature.
    """
    eng = getattr(tree, "_layout_engine", None)
    if eng is None:
        eng = LayoutEngine(tree, backend=backend, **kw)
        object.__setattr__(tree, "_layout_engine", eng)
    return eng
