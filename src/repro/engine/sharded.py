"""Sharded ingestion: parallel shard routing with associative merge.

The qd-tree gives a complete semantic description of every block (paper
Sec 3.2), which makes ingestion shardable: any worker holding a replica of
the routing plan can assign records to blocks independently, and the
per-block aggregates — row counts, min/max tightener partials, categorical
presence masks, advanced-cut truth bits — all merge associatively (sum /
min / max / or over int64 and bool are exact, so the fold is bit-identical
regardless of association or order).  Three pieces:

* :class:`ShardIngestor` routes one shard's micro-batches against the
  tree's compiled plans (shared power-of-two plan-cache buckets — a warmed
  bucket never retraces, no matter which shard hits it) and accumulates a
  serializable :class:`ShardState`: per-block row counts, per-leaf min/max
  tightener partials, and (optionally) per-block row chunks — the spill
  manifest a remote shard would ship back alongside its state.
* :class:`MergeCoordinator` folds ShardStates associatively and publishes
  the merged tightening into the tree — bit-identical to single-stream
  ``LayoutEngine.ingest`` over the same records.
* :func:`sharded_ingest` wires both onto a ``concurrent.futures``
  executor.  ``executor="process"`` — the default for ``n_shards >= 2``
  — takes the real multi-host shape: spawn-context workers in the
  resident module pool hold a :class:`ProcessShardSession` replica of
  the routing plan and ship only the (pure-numpy, pickle/npz-
  serializable) ShardState back to the parent's MergeCoordinator.
  ``executor="thread"`` shares the live engine's compiled plans but
  contends on the GIL (the documented 0.44× footgun —
  :class:`PerformanceWarning`).

The process path streams rounds through a :class:`ProcessShardSession`:
the tree replica is shipped AT MOST ONCE per pool worker per tree
generation (round tasks carry a session token; an unseeded worker raises
:class:`ReplicaMissing` and the parent retries that one task with the
payload attached), and the parent folds ShardStates as they complete —
merge overlaps the slower shards' routing.

Shards route + tighten through the fused single-pass path
(``LayoutEngine.fused_step``) by default — bit-identical to the legacy
two-pass loop, each record touched once.  A shard with no spill buffer
and no observation probe skips the per-row block-id device→host
transfer entirely (``return_bids=False``): the partials it streams back
are aggregates, never rows.

``LayoutService.ingest`` (``IngestOptions(shards=k)``) is the lifecycle
facade over this module; ``repro.coordinator`` folds the same
ShardStates fleet-wide.
"""

from __future__ import annotations

# qdlint: deterministic-module

import atexit
import contextlib
import dataclasses
import itertools
import multiprocessing
import os
import tempfile
import threading
import time
import warnings
from concurrent.futures import (
    Executor,
    ProcessPoolExecutor,
    ThreadPoolExecutor,
    as_completed,
)
from typing import Iterable, Optional

import numpy as np

from repro.core.qdtree import FrozenQdTree, IncrementalTightener
from repro.engine import plan as planlib
from repro.engine.engine import (
    IngestReport,
    LayoutEngine,
    ObservationProbe,
    WindowStat,
    engine_for,
)


class PerformanceWarning(UserWarning):
    """A requested configuration is known to lose wall-clock."""


_THREAD_FOOTGUN = (
    "executor='thread' with n_shards={k}: shard routing shares one GIL, "
    "measured at 0.44x single-stream wall-clock at k=8 "
    "(BENCH_sharded_ingest.json); executor='process' (the default for "
    "n_shards >= 2) routes shards in resident spawn workers instead"
)


def resolve_executor(
    executor: "Executor | str | None",
    n_shards: int,
    stacklevel: int = 3,
) -> "Executor | str":
    """Resolve the sharded-ingest executor default.

    ``None`` picks ``"process"`` for ``n_shards >= 2`` — the only
    executor that wins wall-clock off the GIL — and ``"thread"`` for a
    single shard (no parallelism to lose, no pool to keep resident).
    An explicit ``executor="thread"`` with multiple shards emits
    :class:`PerformanceWarning` citing the measured 0.44× regression,
    but is honored: shared-plan thread shards remain the right tool for
    deterministic tests and for custom ``Executor`` protocols.

    On a TPU host every shard runs in this process: a chip belongs to
    one process, and spawn workers import JAX, so they could neither
    share the parent's chip nor reach another.  ``None`` resolves to
    ``"thread"`` there, and a process executor raises ``ValueError``.
    """
    tpu = planlib.on_tpu()
    if executor is None:
        return "process" if n_shards >= 2 and not tpu else "thread"
    if isinstance(executor, str) and executor not in ("thread", "process"):
        raise ValueError(
            f"executor must be 'thread', 'process', an Executor, or "
            f"None — got {executor!r}"
        )
    if tpu and (
        executor == "process" or isinstance(executor, ProcessPoolExecutor)
    ):
        raise ValueError(
            "a process executor cannot run on a TPU host: the chip belongs "
            "to one process, and spawn workers import JAX — use "
            "executor='thread' or None"
        )
    if executor == "thread" and n_shards > 1 and not tpu:
        warnings.warn(
            _THREAD_FOOTGUN.format(k=n_shards),
            PerformanceWarning,
            stacklevel=stacklevel,
        )
    return executor


@dataclasses.dataclass
class ShardState:
    """One shard's (or a merged set of shards') ingestion aggregates.

    Pure numpy + builtins: picklable for process pools and npz-serializable
    for cross-host shipping.  ``lo``/``hi`` use the IncrementalTightener's
    int64 identity elements (+inf/-inf analogues), so states merge before
    any narrowing to the tree's dtypes.

    ``chunks`` maps BID → list of ``(shard_id, rows)`` buffered row chunks
    (empty when the ingestor ran with ``collect_blocks=False``).  Chunk
    lists concatenate under merge and are sorted by shard id at publish
    time, so block contents are independent of merge order too.

    ``obs`` carries the shard's Eq. 1 skip-rate accounting partial (a
    :class:`~repro.engine.engine.WindowStat`, all-zero when the ingestor
    ran without an observation probe).  Its merge is an exact int sum, so
    the folded window stats are bit-identical to the single-stream
    per-batch sequence for every shard count.
    """

    shard_ids: tuple[int, ...]
    n_leaves: int
    counts: np.ndarray  # (L,) int64 rows routed per block
    lo: np.ndarray  # (L, D) int64 running minima
    hi: np.ndarray  # (L, D) int64 running maxima (exclusive)
    cat: np.ndarray  # (L, bits) bool observed categorical values
    adv: np.ndarray  # (L, A, 2) bool observed advanced-cut truth bits
    n_batches: int
    n_records: int
    chunks: dict[int, list[tuple[int, np.ndarray]]]
    wall_s: float = 0.0
    obs: WindowStat = dataclasses.field(default_factory=WindowStat)

    def merge(self, other: "ShardState") -> "ShardState":
        """Associative, commutative fold of two shard states.

        Every aggregate is an exact elementwise monoid op on int64/bool,
        so ``merge(merge(a, b), c)`` equals ``merge(a, merge(b, c))``
        bit-identically, and the tightening aggregates commute as well.
        """
        if self.n_leaves != other.n_leaves or self.lo.shape != other.lo.shape:
            raise ValueError("cannot merge shard states of different trees")
        overlap = set(self.shard_ids) & set(other.shard_ids)
        if overlap:
            raise ValueError(f"shards merged twice: {sorted(overlap)}")
        chunks: dict[int, list[tuple[int, np.ndarray]]] = {
            b: list(c) for b, c in self.chunks.items()
        }
        for b, c in other.chunks.items():
            chunks.setdefault(b, []).extend(c)
        return ShardState(
            shard_ids=tuple(sorted(self.shard_ids + other.shard_ids)),
            n_leaves=self.n_leaves,
            counts=self.counts + other.counts,
            lo=np.minimum(self.lo, other.lo),
            hi=np.maximum(self.hi, other.hi),
            cat=self.cat | other.cat,
            adv=self.adv | other.adv,
            n_batches=self.n_batches + other.n_batches,
            n_records=self.n_records + other.n_records,
            chunks=chunks,
            wall_s=max(self.wall_s, other.wall_s),
            obs=self.obs.merge(other.obs),
        )

    # -- serialization (cross-host shipping) --------------------------------
    def save(self, path: str) -> None:
        arrays = {
            "shard_ids": np.asarray(self.shard_ids, np.int64),
            "counts": self.counts,
            "lo": self.lo,
            "hi": self.hi,
            "cat": self.cat,
            "adv": self.adv,
            "meta": np.asarray(
                [self.n_leaves, self.n_batches, self.n_records], np.int64
            ),
            "wall_s": np.asarray(self.wall_s),
            "obs": self.obs.to_array(),
        }
        for b, clist in self.chunks.items():
            for sid, rows in clist:
                arrays[f"chunk_{int(sid)}_{int(b)}"] = rows
        np.savez_compressed(path, **arrays)

    @staticmethod
    def load(path: str) -> "ShardState":
        z = np.load(path, allow_pickle=False)
        chunks: dict[int, list[tuple[int, np.ndarray]]] = {}
        for key in z.files:
            if key.startswith("chunk_"):
                _, sid, b = key.split("_")
                chunks.setdefault(int(b), []).append((int(sid), z[key]))
        for clist in chunks.values():
            clist.sort(key=lambda c: c[0])
        meta = z["meta"]
        return ShardState(
            shard_ids=tuple(int(s) for s in z["shard_ids"]),
            n_leaves=int(meta[0]),
            counts=z["counts"],
            lo=z["lo"],
            hi=z["hi"],
            cat=z["cat"],
            adv=z["adv"],
            n_batches=int(meta[1]),
            n_records=int(meta[2]),
            chunks=chunks,
            wall_s=float(z["wall_s"]),
            obs=(
                WindowStat.from_array(z["obs"])
                if "obs" in z.files
                else WindowStat()
            ),
        )


class ShardIngestor:
    """Routes one shard's micro-batches against a replicated plan.

    Holds no shared mutable state: routing reads the (immutable) frozen
    topology through the engine's plan cache, and all accumulation happens
    in a private :class:`IncrementalTightener` that is *never applied* to
    the tree — its partials are extracted into the returned ShardState.
    """

    def __init__(
        self,
        layout: FrozenQdTree | LayoutEngine,
        shard_id: int = 0,
        backend: Optional[str] = None,
        collect_blocks: bool = False,
        probe: Optional[ObservationProbe] = None,
        fused: bool = True,
    ):
        self.engine = (
            layout
            if isinstance(layout, LayoutEngine)
            else engine_for(layout)
        )
        self.shard_id = int(shard_id)
        self.backend = backend
        self.collect_blocks = collect_blocks
        # replicated per-leaf hit counts (engine.observation_probe): every
        # shard scores against the SAME probe arrays, so the summed
        # window-stat partials are bit-identical to single-stream ingest
        self.probe = probe
        self.fused = fused

    def run(self, batches: Iterable[np.ndarray]) -> ShardState:
        """Route every micro-batch; return this shard's aggregates."""
        from repro.data.blocks import BlockBuffers

        tree = self.engine.tree
        tightener = IncrementalTightener(tree)
        # private per-shard buffers reuse the exact routing-order-preserving
        # scatter of the single-stream path (BlockBuffers.append)
        spill = (
            BlockBuffers.for_tree(tree) if self.collect_blocks else None
        )
        n_batches = n_records = 0
        obs = WindowStat()
        # a partials-only shard (no spill, no probe) streams aggregates,
        # never rows — skip the per-row block-id device→host transfer
        need_bids = spill is not None or self.probe is not None
        t0 = time.perf_counter()
        for batch in batches:
            if batch.shape[0] == 0:
                continue
            if self.fused:
                bids, part = self.engine.fused_step(
                    batch, backend=self.backend, return_bids=need_bids
                )
                tightener.merge(part)
            else:
                bids = self.engine.route(batch, backend=self.backend)
                tightener.update(batch, bids)
            if spill is not None:
                spill.append(batch, bids)
            if self.probe is not None:
                obs = obs.merge(self.probe.observe(bids))
            n_batches += 1
            n_records += batch.shape[0]
        chunks = (
            {}
            if spill is None
            else {
                int(b): [(self.shard_id, spill.block(int(b)))]
                for b in np.nonzero(spill.sizes)[0]
            }
        )
        return ShardState(
            shard_ids=(self.shard_id,),
            n_leaves=tree.n_leaves,
            counts=tightener.counts,
            lo=tightener.lo,
            hi=tightener.hi,
            cat=tightener.cat,
            adv=tightener.adv,
            n_batches=n_batches,
            n_records=n_records,
            chunks=chunks,
            wall_s=time.perf_counter() - t0,
            obs=obs,
        )


class MergeCoordinator:
    """Folds ShardStates and publishes the merged tightening into a tree."""

    def __init__(self, tree: FrozenQdTree):
        self.tree = tree
        self._state: Optional[ShardState] = None

    @property
    def merged(self) -> ShardState:
        if self._state is None:
            raise ValueError("no shard states merged yet")
        return self._state

    def add(self, state: ShardState) -> ShardState:
        self._state = state if self._state is None else self._state.merge(state)
        return self._state

    def publish(self, buffers=None) -> np.ndarray:
        """Apply the merged tightening to the tree; returns block sizes.

        Reuses ``IncrementalTightener.apply`` verbatim, so the published
        leaf descriptions — and the description-version bump that evicts
        stale query plans — are exactly what single-stream ``ingest``
        would have produced.  ``buffers`` is forwarded to
        :meth:`fill_buffers`.
        """
        state = self.merged
        t = IncrementalTightener(self.tree)
        t.lo, t.hi = state.lo, state.hi
        t.cat, t.adv = state.cat, state.adv
        t.counts = state.counts
        t.apply()
        if buffers is not None:
            self.fill_buffers(buffers)
        return state.counts.copy()

    def fill_buffers(self, buffers) -> None:
        """Drain the merged spill chunks into ``buffers`` (a BlockBuffers).

        Chunks are folded in shard-id order, so with a contiguous record
        split the buffered blocks match single-stream ingestion
        row-for-row.  Does not touch the tree — usable for what-if runs
        alongside ``tighten=False``.
        """
        state = self.merged
        for b in sorted(state.chunks):
            for _, rows in sorted(state.chunks[b], key=lambda c: c[0]):
                buffers.append_block(b, rows)


@dataclasses.dataclass
class ShardedIngestReport(IngestReport):
    """IngestReport plus shard-parallel accounting.

    (Defaults exist only because the base class now carries a defaulted
    ``observation`` field; :func:`sharded_ingest` always sets these.)

    ``published`` is True iff the merged tightening was applied to the
    tree; ``stale_generation`` is True when a requested publish was
    *skipped* because the caller's ``publish_check`` reported that the
    tree is no longer the live generation (hot-swapped out mid-run) — the
    aggregates in this report are still valid for the captured tree, but
    nothing was mutated.
    """

    n_shards: int = 0
    shard_wall_s: tuple[float, ...] = ()  # per-shard routing wall clock
    merge_s: float = 0.0  # publish step (the fold itself streams,
    # overlapped with routing, so it no longer shows up here)
    published: bool = False
    stale_generation: bool = False
    state: "Optional[ShardState]" = None  # merged partial (keep_state=True)

    @property
    def shard_records_per_s(self) -> float:
        """Aggregate routing throughput of the shard pool (merge excluded)."""
        slowest = max(self.shard_wall_s) if self.shard_wall_s else 0.0
        return self.n_records / slowest if slowest else 0.0


def shard_slices(records: np.ndarray, n_shards: int) -> list[np.ndarray]:
    """Contiguous record split — shard i gets the i-th slice of the stream."""
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    return np.array_split(records, n_shards)


def micro_batches(records: np.ndarray, batch: int):
    for s in range(0, records.shape[0], batch):
        yield records[s : s + batch]


def warm_sizes(n_rows: int, n_shards: int, batch: int) -> set[int]:
    """Every distinct batch size a sharded run will route.

    Derived from :func:`shard_slices` (floor/ceil contiguous split) +
    :func:`micro_batches` (fixed ``batch`` plus a tail remainder), so
    callers can pre-warm exactly the padding buckets the run will hit —
    the zero-retrace warmup used by ``launch/ingest.py`` and
    ``benchmarks/sharded_ingest.py``.
    """
    slice_sizes = {n_rows // n_shards}
    if n_rows % n_shards:
        slice_sizes.add(n_rows // n_shards + 1)
    sizes = {min(batch, s) for s in slice_sizes}
    sizes |= {s % batch for s in slice_sizes}
    return {s for s in sizes if s}


def _run_shard(ingestor: ShardIngestor, batches) -> ShardState:
    """Module-level executor target (keeps futures introspectable)."""
    return ingestor.run(batches)


# -- resident spawn pool -----------------------------------------------------
# ``executor="process"`` used to build (and tear down) a fresh spawn-context
# ProcessPoolExecutor per run, so every run re-paid interpreter start + jax
# import in each worker — the fixed cost that ate the k-shard win in
# BENCH_sharded_ingest.json's process columns.  Workers are stateless
# (each task ships its own tree replica and returns a pure-numpy
# ShardState), so one module-level pool can serve every run; it is built
# lazily at the first ``process_pool`` call, grows (never shrinks) to the
# largest shard count requested, and lives until ``shutdown_process_pool``
# or interpreter exit.
_pool_lock = threading.Lock()
_pool: Optional[ProcessPoolExecutor] = None  # guarded by: _pool_lock
_pool_workers = 0  # guarded by: _pool_lock


def process_pool(min_workers: int = 1) -> ProcessPoolExecutor:
    """The resident spawn pool backing ``executor="process"`` runs.

    Returns a ProcessPoolExecutor with at least ``min_workers`` workers,
    creating or growing it as needed (growth replaces the pool — spawn
    pools cannot resize — after draining the old one).  A pool whose
    workers died (BrokenProcessPool) is rebuilt transparently.
    """
    global _pool, _pool_workers
    if min_workers < 1:
        raise ValueError("min_workers must be >= 1")
    with _pool_lock:
        broken = _pool is not None and getattr(_pool, "_broken", False)
        if _pool is None or broken or _pool_workers < min_workers:
            old = _pool
            workers = max(min_workers, _pool_workers)
            _pool = ProcessPoolExecutor(
                max_workers=workers,
                mp_context=multiprocessing.get_context("spawn"),
            )
            _pool_workers = workers
            if old is not None:
                old.shutdown(wait=not broken)
        return _pool


def shutdown_process_pool(wait: bool = True) -> None:
    """Tear down the resident spawn pool (next use rebuilds it lazily)."""
    global _pool, _pool_workers
    with _pool_lock:
        pool, _pool, _pool_workers = _pool, None, 0
    if pool is not None:
        pool.shutdown(wait=wait)


atexit.register(shutdown_process_pool, wait=False)


# -- resident worker replicas (the session protocol) -------------------------
# Worker-process-side session cache.  Each spawn worker is single-threaded
# (ProcessPoolExecutor runs one task at a time per worker) and the parent
# never touches this dict, so no lock guards it.  Keyed by session token;
# bounded by insertion-order eviction so abandoned sessions cannot pin
# engines forever.
_WORKER_KEEP = 4
_WORKER_STATE: dict[str, dict] = {}

#: parent-side token counter — tokens are identity, never folded into data
_session_ids = itertools.count(1)


class ReplicaMissing(RuntimeError):
    """A pool worker was handed a round for a session it has not been
    seeded with.  The parent catches this and retries that ONE task with
    the ``(tree, records_path)`` payload attached — the
    ship-until-confirmed protocol that bounds replica pickling to at
    most once per worker per session."""


def _worker_entry(token: str, tree, records_path: Optional[str]) -> dict:
    """Fetch-or-install this worker's session entry (idempotent)."""
    entry = _WORKER_STATE.get(token)
    if entry is None:
        entry = {
            "engine": engine_for(tree),
            "records": None,
            "warmed": set(),
        }
        _WORKER_STATE[token] = entry
        while len(_WORKER_STATE) > _WORKER_KEEP:
            evict = next(iter(_WORKER_STATE))
            if evict == token:
                break
            del _WORKER_STATE[evict]
    if records_path is not None and entry["records"] is None:
        # memory-map: k workers on one host share the page cache instead
        # of holding k private copies of the staged stream
        entry["records"] = np.load(records_path, mmap_mode="r")
    return entry


def _worker_seed(
    token: str, tree, records_path: Optional[str], linger_s: float = 0.0
) -> int:
    """Idempotently install the session replica in this pool worker.

    ``linger_s``: an already-seeded worker naps briefly before returning,
    so a wave of seed tasks drains toward the workers that still need
    one (a ProcessPoolExecutor cannot target a specific worker).
    Returns this worker's pid, the parent's coverage receipt.
    """
    if token in _WORKER_STATE and linger_s > 0.0:
        time.sleep(linger_s)
    _worker_entry(token, tree, records_path)
    return os.getpid()


def _worker_round(
    token: str,
    shard_id: int,
    n_shards: int,
    rows: Optional[np.ndarray],
    batch: int,
    backend: Optional[str],
    collect_blocks: bool,
    probe: Optional[ObservationProbe],
    fused: bool,
    seed=None,  # (tree, records_path) | None — ReplicaMissing retry payload
) -> tuple[int, ShardState]:
    """Run one shard round against this worker's resident session engine.

    ``rows`` is the shard's record slice (shipped mode) or None (staged
    mode: the worker slices its resident record array locally, so the
    task carries no rows at all).  Plans warm incrementally per distinct
    batch size, once per worker per session — a warmed bucket never
    retraces, no matter which shard lands here next round.
    """
    if token not in _WORKER_STATE and seed is None:
        raise ReplicaMissing(token)
    entry = _worker_entry(
        token, *(seed if seed is not None else (None, None))
    )
    engine = entry["engine"]
    if rows is None:
        if entry["records"] is None:
            raise ReplicaMissing(token)  # staged round, nothing staged here
        rows = shard_slices(entry["records"], n_shards)[shard_id]
    need = warm_sizes(rows.shape[0], 1, batch) - entry["warmed"]
    if need:
        if fused:
            engine.warm_ingest(need, backend=backend)
        else:
            d = engine.tree.leaf_lo.shape[1]
            for s in sorted(need):
                engine.route(np.zeros((s, d), np.int32), backend=backend)
        entry["warmed"] |= need
    ingestor = ShardIngestor(
        engine, shard_id=shard_id, backend=backend,
        collect_blocks=collect_blocks, probe=probe, fused=fused,
    )
    return os.getpid(), ingestor.run(micro_batches(rows, batch))


def _unlink_quiet(path: str) -> None:
    with contextlib.suppress(OSError):
        os.unlink(path)


class ProcessShardSession:
    """Parent-side handle streaming sharded rounds to the resident pool.

    The old process path re-pickled the tree replica into every task of
    every run — the fixed cost that made ``executor="process"`` lose
    wall-clock (BENCH_sharded_ingest.json).  A session ships the replica
    AT MOST ONCE per pool worker per tree generation: round tasks carry
    only a token; a worker that has not been seeded raises
    :class:`ReplicaMissing` and the parent retries that one task with
    the payload attached.  Ingest/routing plan keys do not include leaf
    descriptions, so a worker's warm plans stay valid across the
    parent's tightening publishes — a session lives until the tree
    object itself is replaced (rebuild / hot swap), when the owner
    builds a new session (``LayoutService`` does this automatically).

    :meth:`stage` additionally spills the stream to a temp ``.npy`` once
    and has workers memory-map it, so steady-state rounds move only the
    token-sized task and one ~25 KB ShardState reply per shard — the
    fleet-worker shape ``benchmarks/coordinator.py`` measures.

    Thread-safe: concurrent :meth:`round` calls are independent; the
    shared counters below are folded under the session lock.
    """

    def __init__(
        self,
        layout: FrozenQdTree | LayoutEngine,
        n_shards: int,
        batch: int = 2048,
        backend: Optional[str] = None,
        fused: bool = True,
    ):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.engine = (
            layout
            if isinstance(layout, LayoutEngine)
            else engine_for(layout)
        )
        self.n_shards = int(n_shards)
        self.batch = int(batch)
        self.backend = backend
        self.fused = bool(fused)
        self.replica = replicate_tree(self.engine.tree)
        self.token = f"shardsess-{os.getpid()}-{next(_session_ids)}"
        self._lock = threading.Lock()
        self._records_path: Optional[str] = None  # guarded by: self._lock
        self._seeded: set[int] = set()  # guarded by: self._lock -- confirmed worker pids
        self._reseeds = 0  # guarded by: self._lock -- ReplicaMissing retries served
        self._rounds = 0  # guarded by: self._lock
        self._closed = False  # guarded by: self._lock

    @property
    def pool(self) -> ProcessPoolExecutor:
        """The resident module pool, grown to this session's shard count."""
        return process_pool(self.n_shards)

    def stats(self) -> dict:
        with self._lock:
            return {
                "token": self.token,
                "rounds": self._rounds,
                "reseeds": self._reseeds,
                "seeded_workers": len(self._seeded),
                "staged": self._records_path is not None,
            }

    def stage(self, records: np.ndarray, max_waves: int = 16) -> int:
        """Make ``records`` resident in the pool workers.

        Spills the array to a temp ``.npy`` once (workers memory-map it
        — one page-cache copy per host, not one per worker), then
        pre-seeds the pool.  Subsequent ``round(None)`` calls slice the
        staged stream worker-side.  Returns confirmed worker count.
        """
        fd, path = tempfile.mkstemp(prefix="qdshard-", suffix=".npy")
        os.close(fd)
        np.save(path, np.ascontiguousarray(records))
        with self._lock:
            if self._closed:
                _unlink_quiet(path)
                raise RuntimeError("session is closed")
            old, self._records_path = self._records_path, path
        if old is not None:
            _unlink_quiet(old)
        return self.seed(max_waves=max_waves)

    def seed(self, max_waves: int = 16, linger_s: float = 0.02) -> int:
        """Best-effort pre-seed of every pool worker.

        Waves of idempotent seed tasks; already-seeded workers linger
        briefly so the queue drains toward unseeded ones.  Correctness
        never depends on coverage — an unseeded worker is caught by the
        ReplicaMissing retry in :meth:`round` — this just keeps
        first-round timings honest.  Returns confirmed worker count.
        """
        pool = self.pool
        with self._lock:
            path = self._records_path
        procs = getattr(pool, "_processes", None)
        target = len(procs) if procs else self.n_shards
        for _ in range(max_waves):
            with self._lock:
                if len(self._seeded) >= target:
                    break
            futs = [
                pool.submit(
                    _worker_seed, self.token, self.replica, path, linger_s
                )
                for _ in range(self.n_shards)
            ]
            pids = [f.result() for f in futs]
            with self._lock:
                self._seeded.update(pids)
        with self._lock:
            return len(self._seeded)

    def round(
        self,
        records: Optional[np.ndarray] = None,
        collect_blocks: bool = False,
        probe: Optional[ObservationProbe] = None,
        fold=None,  # Callable[[ShardState], None] | None
    ) -> list[ShardState]:
        """Run one k-shard routing round; returns states in shard order.

        ``records=None`` uses the staged stream (each worker slices its
        resident copy locally); otherwise the given array is split and
        its slices shipped with the tasks.  ``fold`` (if given) is
        called with each ShardState as it completes, so the parent's
        associative merge overlaps the slower shards' routing instead of
        waiting for the full barrier (the merge commutes bit-exactly, so
        completion order cannot change the result).
        """
        with self._lock:
            if self._closed:
                raise RuntimeError("session is closed")
            path = self._records_path
        if records is None and path is None:
            raise ValueError(
                "no records given and none staged; call stage() first"
            )
        parts = (
            shard_slices(records, self.n_shards)
            if records is not None
            else None
        )
        pool = self.pool

        def _submit(i: int, seed):
            rows = parts[i] if parts is not None else None
            return pool.submit(
                _worker_round, self.token, i, self.n_shards, rows,
                self.batch, self.backend, collect_blocks, probe,
                self.fused, seed,
            )

        pending = {_submit(i, None): i for i in range(self.n_shards)}
        states: dict[int, ShardState] = {}
        pids: list[int] = []
        reseeds = 0
        while pending:
            for fut in as_completed(list(pending)):
                i = pending.pop(fut)
                try:
                    pid, state = fut.result()
                except ReplicaMissing:
                    # that worker has not seen this session yet: re-ship
                    # the replica (and staged-records path) to it once
                    reseeds += 1
                    pending[_submit(i, (self.replica, path))] = i
                    continue
                states[i] = state
                pids.append(pid)
                if fold is not None:
                    fold(state)
        with self._lock:
            self._rounds += 1
            self._reseeds += reseeds
            self._seeded.update(pids)
        return [states[i] for i in range(self.n_shards)]

    def close(self) -> None:
        """Release the staged temp file; the pool (shared) stays up and
        the workers' cached engines age out via the bounded session
        cache."""
        with self._lock:
            self._closed = True
            path, self._records_path = self._records_path, None
        if path is not None:
            _unlink_quiet(path)

    def __enter__(self) -> "ProcessShardSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _process_shard_worker(
    tree: FrozenQdTree,
    part: np.ndarray,
    shard_id: int,
    batch: int,
    backend: Optional[str],
    collect_blocks: bool,
    probe: Optional[ObservationProbe],
    fused: bool,
) -> ShardState:
    """Process-pool target: rebuild a ShardIngestor against the replica.

    Runs in a spawn-context worker with no shared state: the tree replica,
    the shard's record slice, and the (pure-numpy) probe all arrive by
    pickle; only the ShardState ships back.  Plans are warmed before the
    timed run so a worker's first-compile cost never lands in ``wall_s``
    (the parent's trace counters are untouched either way — compiles
    happen in the worker process).
    """
    engine = engine_for(tree)
    if fused:
        engine.warm_ingest(
            warm_sizes(part.shape[0], 1, batch), backend=backend
        )
    else:
        for s in sorted(warm_sizes(part.shape[0], 1, batch)):
            engine.route(
                np.zeros((s, tree.leaf_lo.shape[1]), np.int32),
                backend=backend,
            )
    ingestor = ShardIngestor(
        engine, shard_id=shard_id, backend=backend,
        collect_blocks=collect_blocks, probe=probe, fused=fused,
    )
    return ingestor.run(micro_batches(part, batch))


def sharded_ingest(
    layout: FrozenQdTree | LayoutEngine,
    records: np.ndarray,
    n_shards: int,
    batch: int = 2048,
    executor: "Executor | str | None" = None,
    collect_blocks: bool = False,
    buffers=None,  # data.blocks.BlockBuffers | None
    tighten: bool = True,
    backend: Optional[str] = None,
    lock=None,  # context manager guarding the publish step
    observe=None,  # Workload | WorkloadTensors | ObservationProbe | None
    publish_check=None,  # Callable[[], bool], evaluated under ``lock``
    fused: bool = True,
    session: Optional[ProcessShardSession] = None,
    keep_state: bool = False,
) -> ShardedIngestReport:
    """Shard ``records`` across parallel ingestors and merge associatively.

    Contiguously splits the stream into ``n_shards``, runs one
    :class:`ShardIngestor` per shard on ``executor`` (resident spawn
    workers by default for ``n_shards >= 2``; see below), folds the
    resulting ShardStates through a
    :class:`MergeCoordinator`, and (when ``tighten``) publishes the merged
    tightening — bit-identical to ``LayoutEngine.ingest`` over the same
    records for every k.  With ``tighten=False`` the tree is left
    untouched (same contract as ``ingest``): buffers still fill and the
    merged counts/partials are still computed and reported.

    With ``observe`` set, one :class:`ObservationProbe` is built from the
    engine's compiled plan and replicated to every shard; the merged
    Eq. 1 window-stat partial lands in ``report.observation`` —
    bit-identical to the single-stream ``ingest(observe=...)`` totals.

    ``publish_check`` guards against publishing into a tree that was
    hot-swapped out mid-run: it is evaluated under ``lock`` immediately
    before the tightening is applied, and if it returns False the publish
    is skipped and the report carries ``stale_generation=True`` (see
    ``LayoutService.ingest``).  ``keep_state=True`` attaches the merged
    :class:`ShardState` to ``report.state`` — the seam fleet callers use
    to forward the partial to a ``repro.coordinator.FleetCoordinator``
    (typically with ``tighten=False``: route here, publish there).

    ``executor`` selects the pool.  ``None`` resolves via
    :func:`resolve_executor`: ``"process"`` for ``n_shards >= 2`` off the
    TPU, ``"thread"`` otherwise.  ``"process"`` takes the multi-host
    shape — spawn-context workers in the RESIDENT module pool
    (:func:`process_pool`) run against a :class:`ProcessShardSession`
    replica (shipped at most once per worker — pass ``session=`` to
    reuse a seeded session across runs; a fresh per-run session is
    created otherwise) and ship ShardStates back, so nothing unpicklable
    ever crosses the process boundary and shard routing escapes the GIL.
    ``"thread"`` shares the live engine's compiled plans but serializes
    routing on the GIL — the documented 0.44× footgun
    (:class:`PerformanceWarning`).  A ``ProcessPoolExecutor`` instance
    keeps the legacy per-task replica shipping; any other ``Executor``
    instance drives the shared-plan ``.map`` protocol.

    The parent folds ShardStates AS THEY COMPLETE (``as_completed``
    streaming into the MergeCoordinator), so the associative merge
    overlaps the slower shards' routing; the fold commutes bit-exactly,
    so completion order cannot change the published result.
    """
    engine = (
        layout if isinstance(layout, LayoutEngine) else engine_for(layout)
    )
    if session is not None:
        if (
            session.n_shards != n_shards
            or session.batch != batch
            or session.fused != fused
            or session.engine.tree is not engine.tree
        ):
            raise ValueError(
                "session does not match this run's tree/shards/batch/fused"
            )
        executor = resolve_executor("process", n_shards)
    else:
        executor = resolve_executor(executor, n_shards)
    if buffers is not None:
        collect_blocks = True
    traces0 = planlib.trace_counts()
    probe = (
        engine.observation_probe(observe, backend=backend)
        if observe is not None
        else None
    )
    coordinator = MergeCoordinator(engine.tree)
    t0 = time.perf_counter()
    if executor == "process" and session is None:
        session_own = ProcessShardSession(
            engine, n_shards, batch=batch, backend=backend, fused=fused
        )
    else:
        session_own = None
    if executor == "process":
        sess = session if session is not None else session_own
        try:
            states = sess.round(
                records, collect_blocks=collect_blocks, probe=probe,
                fold=coordinator.add,
            )
        finally:
            if session_own is not None:
                session_own.close()
    elif isinstance(executor, ProcessPoolExecutor):
        # legacy stateless shape: the replica ships with every task
        replica = replicate_tree(engine.tree)
        shard_parts = shard_slices(records, n_shards)
        args = [
            (replica, shard_parts[i], i, batch, backend, collect_blocks,
             probe, fused)
            for i in range(n_shards)
        ]
        states = [
            f.result()
            for f in [
                executor.submit(_process_shard_worker, *a) for a in args
            ]
        ]
        for state in states:
            coordinator.add(state)
    else:
        shard_parts = shard_slices(records, n_shards)
        ingestors = [
            ShardIngestor(
                engine, shard_id=i, backend=backend,
                collect_blocks=collect_blocks, probe=probe, fused=fused,
            )
            for i in range(n_shards)
        ]
        shard_batches = [micro_batches(part, batch) for part in shard_parts]
        if executor == "thread":
            by_shard: dict[int, ShardState] = {}
            with ThreadPoolExecutor(max_workers=n_shards) as pool:
                futs = {
                    pool.submit(_run_shard, ing, b): i
                    for i, (ing, b) in enumerate(
                        zip(ingestors, shard_batches)
                    )
                }
                for fut in as_completed(futs):
                    state = fut.result()
                    by_shard[futs[fut]] = state
                    coordinator.add(state)
            states = [by_shard[i] for i in range(n_shards)]
        else:
            # custom Executor instances keep the .map protocol (tests
            # interpose here to exercise swap-during-run races)
            states = list(
                executor.map(_run_shard, ingestors, shard_batches)
            )
            for state in states:
                coordinator.add(state)
    t_merge = time.perf_counter()
    published = stale = False
    if tighten:
        # publish under the caller's lock; re-check liveness there — the
        # tree may have been hot-swapped out while the shards were routing,
        # and tightening a non-live tree would go unannounced otherwise
        with (lock if lock is not None else contextlib.nullcontext()):
            if publish_check is None or publish_check():
                sizes = coordinator.publish(buffers=buffers)
                published = True
            else:
                stale = True
    if not published:
        if buffers is not None:
            coordinator.fill_buffers(buffers)
        sizes = coordinator.merged.counts.copy()
    t1 = time.perf_counter()
    delta = planlib.trace_delta(traces0, planlib.trace_counts())
    merged = coordinator.merged
    return ShardedIngestReport(
        n_batches=merged.n_batches,
        n_records=merged.n_records,
        block_sizes=sizes,
        wall_s=t1 - t0,
        backend=backend or engine.backend,
        plan_cache=engine.plans.stats(),
        traces=delta,
        observation=merged.obs if probe is not None else None,
        n_shards=n_shards,
        shard_wall_s=tuple(s.wall_s for s in states),
        merge_s=t1 - t_merge,
        published=published,
        stale_generation=stale,
        # the merged partial itself, for callers that forward it to a
        # fleet coordinator (repro.coordinator) instead of publishing here
        state=merged if keep_state else None,
    )


def replicate_tree(tree: FrozenQdTree) -> FrozenQdTree:
    """A routing-identical replica with private leaf descriptions.

    The copy a shard host (or a what-if run) would hold: topology and cut
    table are shared (immutable), leaf descriptions are cloned so the
    replica can be tightened without touching the original.  The replica
    gets its own tree signature, hence its own plan-cache entries.
    """
    return FrozenQdTree(
        schema=tree.schema,
        cuts=tree.cuts,
        cut_id=tree.cut_id.copy(),
        left=tree.left.copy(),
        right=tree.right.copy(),
        leaf_bid=tree.leaf_bid.copy(),
        leaf_lo=tree.leaf_lo.copy(),
        leaf_hi=tree.leaf_hi.copy(),
        leaf_cat=tree.leaf_cat.copy(),
        leaf_adv=tree.leaf_adv.copy(),
        depth=tree.depth,
    )


def states_bit_identical(a: ShardState, b: ShardState) -> bool:
    """True iff two states' tightening aggregates are bit-identical."""
    return (
        bool(np.array_equal(a.counts, b.counts))
        and bool(np.array_equal(a.lo, b.lo))
        and bool(np.array_equal(a.hi, b.hi))
        and bool(np.array_equal(a.cat, b.cat))
        and bool(np.array_equal(a.adv, b.adv))
    )


__all__ = [
    "MergeCoordinator",
    "PerformanceWarning",
    "ProcessShardSession",
    "ReplicaMissing",
    "ShardIngestor",
    "ShardState",
    "ShardedIngestReport",
    "micro_batches",
    "process_pool",
    "replicate_tree",
    "resolve_executor",
    "shard_slices",
    "sharded_ingest",
    "shutdown_process_pool",
    "states_bit_identical",
    "warm_sizes",
]
