"""Serving-tier tests: exact-signature cache soundness (equal signatures
route identically, re-canonicalization is a fixed point), epoch-keyed
invalidation (hot swap and in-place tighten each retire cached results),
a stale-read hammer under concurrent swaps, admission/coalescing
semantics, and the cached-traffic → WorkloadTracker observation contract
(drift scoring itself stays ingest-side; serving influences it only
through the tracker-inferred workload)."""

import threading
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 containers without hypothesis
    from tests._hypothesis_shim import given, settings, st

from repro.core import query as qry
from repro.core.predicates import OP_GE, OP_LT
from repro.core.query import InAtom, Query, RangeAtom
from repro.engine import plan as planlib
from repro.serve import (
    EXACT_RESOLUTION,
    AdmissionError,
    QueryServer,
    RequestQueue,
    ResultCache,
    ServeConfig,
    exact_signatures,
)
from repro.service import Epoch, LayoutService, build_layout
from repro.service.tracker import query_from_signature
from tests.test_qdtree import small_setup
from tests.test_query import random_query


def _setup(seed=0, n_queries=8):
    schema, records, cuts = small_setup(seed)
    rng = np.random.default_rng(seed)
    work = qry.Workload(
        schema, tuple(random_query(schema, rng) for _ in range(n_queries))
    )
    return schema, records, cuts, work


def _service(seed=0, n_queries=8, backend="numpy", min_block=30):
    schema, records, cuts, work = _setup(seed, n_queries)
    svc = LayoutService.build(
        records, work, strategy="greedy", cuts=cuts, backend=backend,
        min_block=min_block,
    )
    return schema, records, cuts, work, svc


def _sig1(schema, q, cuts=None):
    return exact_signatures(qry.Workload(schema, (q,)), cuts)[0]


# ---------------------------------------------------------------------------
# Exact signatures: the cache-key soundness properties
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_equal_signatures_route_identically(seed):
    """Two textually different queries whose atoms fold to the same
    canonical form share an exact signature AND route to bit-identical
    block IDs — the property that makes signature-keyed result reuse
    sound."""
    schema, records, cuts, work = _setup(3)
    build = build_layout(
        records, work, strategy="greedy", cuts=cuts, min_block=30
    )
    rng = np.random.default_rng(seed)
    lo = int(rng.integers(0, 32))
    hi = lo + int(rng.integers(1, 32))
    a1 = RangeAtom(0, OP_GE, lo)
    a2 = RangeAtom(0, OP_LT, hi)
    a3 = InAtom(2, (0, 2, 4))
    q1 = Query.conjunction([a1, a2, a3])
    # reordered and with a redundant duplicate atom: min/max folding and
    # value-set intersection canonicalize both to one form
    q2 = Query.conjunction([a3, a2, a1, RangeAtom(0, OP_GE, lo)])
    s1 = _sig1(schema, q1, build.tree.cuts)
    s2 = _sig1(schema, q2, build.tree.cuts)
    assert s1 == s2
    eng = build.tree
    np.testing.assert_array_equal(
        qry.route_query(eng, q1), qry.route_query(eng, q2)
    )


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_exact_signature_recanonicalization_fixed_point(seed):
    """Materializing a query back from its exact signature and re-signing
    it reproduces the signature exactly — at EXACT_RESOLUTION the
    bucketing maps are the identity, so canonicalization is lossless and
    idempotent."""
    schema, _, _, _ = _setup(3)
    rng = np.random.default_rng(seed)
    q = random_query(schema, rng)
    sig = _sig1(schema, q)  # no cut filter: keep every advanced atom
    rebuilt = query_from_signature(sig, schema)
    assert _sig1(schema, rebuilt) == sig
    assert EXACT_RESOLUTION > max(c.dom for c in schema.columns)


# ---------------------------------------------------------------------------
# ResultCache: epoch keying, LRU, stale-put rejection
# ---------------------------------------------------------------------------
def test_result_cache_epoch_lifecycle():
    cache = ResultCache(capacity=8)
    e1, e2 = Epoch(1, 0), Epoch(2, 0)
    bids = np.arange(3, dtype=np.int32)

    # puts before any activation are stale (no live epoch yet)
    assert not cache.put(e1, ("sig",), bids)
    assert cache.stats.stale_puts == 1

    cache.activate(e1)
    assert cache.put(e1, ("sig",), bids)
    got = cache.get(e1, ("sig",))
    np.testing.assert_array_equal(got, bids)
    assert not got.flags.writeable  # shared by reference, read-only
    assert cache.stats.hits == 1

    # a swap retires every e1 entry; e1 results computed in-flight are
    # rejected rather than poisoning the new generation
    cache.activate(e2)
    assert len(cache) == 0
    assert cache.stats.invalidated == 1
    assert cache.get(e1, ("sig",)) is None
    assert not cache.put(e1, ("sig",), bids)
    assert cache.stats.stale_puts == 2
    assert cache.stats.epoch_changes == 2


def test_result_cache_lru_eviction_and_get_many_parity():
    cache = ResultCache(capacity=2)
    e = Epoch(1, 0)
    cache.activate(e)
    for i in range(3):
        cache.put(e, (i,), np.array([i], np.int32))
    assert len(cache) == 2
    assert cache.stats.evictions == 1
    assert cache.get(e, (0,)) is None  # oldest evicted

    many = cache.get_many(e, [(1,), (2,), (0,)])
    np.testing.assert_array_equal(many[0], [1])
    np.testing.assert_array_equal(many[1], [2])
    assert many[2] is None
    assert cache.stats.hits == cache.stats.hits  # counters consistent
    single = [cache.get(e, s) for s in [(1,), (2,), (0,)]]
    for a, b in zip(many, single):
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(a, b)


def test_result_cache_rejects_bad_capacity():
    with pytest.raises(ValueError):
        ResultCache(capacity=0)


# ---------------------------------------------------------------------------
# Admission + coalescing
# ---------------------------------------------------------------------------
def test_admission_queue_and_tenant_bounds():
    cfg = ServeConfig(max_batch=4, max_queue=4, max_per_tenant=2)
    queue = RequestQueue(cfg)
    schema, _, _, work = _setup(5)
    q = work.queries[0]

    queue.submit(q, tenant="a")
    queue.submit(q, tenant="a")
    with pytest.raises(AdmissionError) as exc:
        queue.submit(q, tenant="a")
    assert exc.value.reason == "tenant"
    queue.submit(q, tenant="b")  # fairness: other tenants still admitted
    queue.submit(q, tenant="c")
    with pytest.raises(AdmissionError) as exc:
        queue.submit(q, tenant="d")
    assert exc.value.reason == "queue"
    assert queue.stats.rejected_tenant == 1
    assert queue.stats.rejected_queue == 1
    assert queue.stats.accepted == 4


def test_submit_many_keeps_admitted_prefix_on_rejection():
    cfg = ServeConfig(max_batch=4, max_queue=3)
    queue = RequestQueue(cfg)
    _, _, _, work = _setup(5)
    with pytest.raises(AdmissionError):
        queue.submit_many([work.queries[0]] * 5)
    assert queue.stats.accepted == 3  # prefix admitted, identical to a
    assert len(queue) == 3            # submit() loop's behavior
    batch = queue.next_batch(timeout=0)
    assert len(batch) == 3
    queue.release_many(batch)
    assert queue.inflight("default") == 0


def test_sync_serve_batch_chunks_at_max_batch():
    _, _, _, work, svc = _service(7, n_queries=6)
    server = QueryServer(svc, ServeConfig(max_batch=8))
    qs = [work.queries[i % len(work)] for i in range(8 * 2 + 3)]
    results = server.serve_batch(qs)
    assert len(results) == 19
    assert server.counters.dispatches == 3  # 8 + 8 + 3
    assert server.counters.queries_served == 19
    server.stop()


def test_async_deadline_coalesces_a_trickle():
    _, _, _, work, svc = _service(7, n_queries=6)
    server = QueryServer(
        svc, ServeConfig(max_batch=32, max_delay_s=0.1)
    ).start()
    tickets = [server.submit(work.queries[i % 3]) for i in range(3)]
    for t in tickets:
        t.result(timeout=10.0)
    # all three arrived well inside the oldest waiter's deadline, so the
    # dispatcher served them as ONE coalesced engine visit
    assert server.counters.dispatches == 1
    server.stop()
    with pytest.raises(RuntimeError):
        server.start()  # stopped servers don't resurrect


# ---------------------------------------------------------------------------
# Epoch invalidation: hot swap and in-place tighten
# ---------------------------------------------------------------------------
def test_hot_swap_retires_prior_generation_entries():
    _, records, cuts, work, svc = _service(11)
    server = QueryServer(svc, ServeConfig(max_batch=8))
    qs = list(work.queries[:4])
    server.serve_batch(qs)
    r2 = server.serve_batch(qs)
    assert all(r.cached for r in r2)
    old_epoch = svc.live_epoch()

    other = build_layout(
        records, work, strategy="greedy", cuts=cuts, min_block=60
    )
    gen = svc.swap(other)
    # the swap listener purged eagerly; prior-generation keys are
    # unreachable regardless, because lookups carry the live epoch
    assert server.cache.epoch == svc.live_epoch()
    assert server.cache.stats.invalidated > 0
    assert server.cache.get(old_epoch, ("anything",)) is None

    r3 = server.serve_batch(qs)
    assert not any(r.cached for r in r3)  # cold at the new generation
    assert all(r.generation == gen for r in r3)
    for q, r in zip(qs, r3):
        np.testing.assert_array_equal(
            r.bids, svc.version(gen).engine.route_query(q)
        )
    server.stop()


def test_tighten_bumps_epoch_and_refreshes_results():
    _, records, _, work, svc = _service(13)
    server = QueryServer(svc, ServeConfig(max_batch=8))
    qs = list(work.queries[:4])
    server.serve_batch(qs)
    assert all(r.cached for r in server.serve_batch(qs))

    live = svc.live_version()
    v0 = planlib.desc_version(live.tree)
    live.tree.tighten(records, live.engine.route(records))
    assert planlib.desc_version(live.tree) == v0 + 1

    # same generation, new desc_version: the next dispatch activates the
    # new epoch, so every entry from (gen, v0) is unreachable and the
    # batch re-routes against the tightened descriptions
    r = server.serve_batch(qs)
    assert not any(x.cached for x in r)
    assert all(x.desc_version == v0 + 1 for x in r)
    for q, x in zip(qs, r):
        np.testing.assert_array_equal(x.bids, live.engine.route_query(q))
    assert all(x.cached for x in server.serve_batch(qs))  # re-cached
    server.stop()


# ---------------------------------------------------------------------------
# Stale-read hammer: swaps under live concurrent traffic
# ---------------------------------------------------------------------------
def test_stale_read_hammer_under_concurrent_swaps():
    _, records, cuts, work, svc = _service(17, n_queries=10)
    builds = [
        build_layout(records, work, strategy="greedy", cuts=cuts,
                     min_block=mb)
        for mb in (40, 70)
    ]
    server = QueryServer(
        svc, ServeConfig(max_batch=8, max_delay_s=0.002)
    ).start()
    pairs = []
    lock = threading.Lock()
    errors = []

    def client(tid):
        rng = np.random.default_rng(100 + tid)
        mine = []
        try:
            for _ in range(40):
                q = work.queries[int(rng.integers(0, len(work)))]
                mine.append((q, server.serve(q, tenant=f"t{tid}",
                                             timeout=30.0)))
        except BaseException as e:
            errors.append(e)
        with lock:
            pairs.extend(mine)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for i in range(4):  # hot swaps under live traffic
        time.sleep(0.01)
        svc.swap(builds[i % 2])
    for t in threads:
        t.join()
    server.stop()
    assert not errors, errors[0]
    assert len(pairs) == 120
    # the serving contract: zero stale responses, and every response is
    # bit-identical to routing that query on its provenance generation
    assert server.counters.stale_responses == 0
    for q, res in pairs:
        np.testing.assert_array_equal(
            res.bids, svc.version(res.generation).engine.route_query(q)
        )


# ---------------------------------------------------------------------------
# Cached traffic still feeds workload observation (drift stays ingest-side)
# ---------------------------------------------------------------------------
def test_cache_hits_record_into_tracker():
    """Serving records EVERY query — hit or miss — into the tracker, so
    workload inference never goes blind behind a hot cache.  Drift
    *scoring* (skip-rate monitoring) remains ingest-side by design: the
    serving tier influences rebuilds only through the tracker-inferred
    workload, exactly like ``launch.serve --workload auto`` drives
    ``service.rebuild(records, tracker.infer_workload())``."""
    _, records, _, work, svc = _service(19, n_queries=6)
    tracker = svc.workload_tracker()
    server = QueryServer(svc, ServeConfig(max_batch=8), tracker=tracker)
    qs = list(work.queries[:4])
    server.serve_batch(qs)
    seen1 = tracker.snapshot().queries_seen
    assert seen1 == 4
    r = server.serve_batch(qs)  # pure cache hits
    assert all(x.cached for x in r)
    assert tracker.snapshot().queries_seen == 8  # hits recorded too
    inferred = tracker.infer_workload()
    assert len(inferred) > 0
    # and the inferred mix is actually buildable — the auto-rebuild loop
    rep = svc.rebuild(records, inferred, min_block=30)
    assert rep.old_generation == 1
    server.stop()


def test_serve_stats_surface():
    _, _, _, work, svc = _service(23)
    tracker = svc.workload_tracker()
    server = QueryServer(svc, ServeConfig(max_batch=8), tracker=tracker)
    server.warm(work)
    server.serve_batch(list(work.queries[:3]))
    stats = server.stats()
    assert stats["queue_depth"] == 0
    assert stats["epoch"] == list(svc.live_epoch())
    assert stats["cache"]["lookups"] == 3
    assert stats["queue_wait_ms"] == pytest.approx(
        stats["counters"]["queue_wait_us"] / 3 / 1e3)
    assert stats["counters"]["queries_served"] == 3
    assert stats["admission"]["accepted"] == 3
    res = server.serve(work.queries[0])
    assert res.epoch == svc.live_epoch()
    server.stop()
    # post-stop: admission is closed
    with pytest.raises(RuntimeError):
        server.submit(work.queries[0])
