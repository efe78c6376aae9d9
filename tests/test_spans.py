"""The program's profiler spans and the server's queue-wait counter.

A small ``LayoutService`` ingest and a ``QueryServer`` (the sync
``serve_batch`` path and the threaded dispatcher) run under a JAX
profiler trace; the ``.xplane.pb`` it writes must hold every span of
``repro.spans`` in the counts and nesting the names promise.  A fake
clock pins ``ServerCounters.queue_wait_us`` exactly, across a swap retry.
"""

import glob
import os

import jax
import numpy as np
import pytest

from repro import spans
from repro.core import query as qry
from repro.serve import QueryServer, ServeConfig
from repro.service import LayoutService
from tests.test_qdtree import small_setup
from tests.test_query import random_query

BATCH = 64


def _service(seed=5, backend="jax", n_queries=8):
    schema, records, cuts = small_setup(seed)
    rng = np.random.default_rng(seed)
    work = qry.Workload(
        schema, tuple(random_query(schema, rng) for _ in range(n_queries))
    )
    svc = LayoutService.build(records, work, strategy="greedy", cuts=cuts,
                              backend=backend, min_block=30)
    return records, work, svc


def _batches(records):
    return [records[i:i + BATCH] for i in range(0, len(records), BATCH)]


def _host_lines(trace_dir):
    """{line index: [(start, end, name), ...]} of the program's spans on
    the host plane of the newest trace under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    names = set(spans.ENGINE + spans.SERVE)
    lines = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.start_ns, e.end_ns, e.name) for e in line.events
                   if e.name in names]
            if evs:
                lines[i] = evs
    return lines


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced session: two ingest calls, a sync burst and threaded
    submissions; what the program reported, and the spans by line."""
    records, work, svc = _service()
    server = QueryServer(svc, ServeConfig(max_batch=4))
    server.warm(work)
    batches = _batches(records)
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        reports = [svc.ingest(iter(batches)), svc.ingest(iter(batches[:3]))]
        server.serve_batch(list(work.queries))
        server.start()
        tickets = [server.submit(q) for q in work.queries[:3]]
        for t in tickets:
            t.result(timeout=30.0)
        server.stop()
    return reports, server.counters, _host_lines(str(out))


def _count(lines, name):
    return sum(1 for evs in lines.values() for e in evs if e[2] == name)


def test_every_span_appears(traced):
    _, _, lines = traced
    seen = {e[2] for evs in lines.values() for e in evs}
    assert seen == set(spans.ENGINE + spans.SERVE)


def test_ingest_spans_count_batches_and_calls(traced):
    reports, _, lines = traced
    n_batches = sum(r.n_batches for r in reports)
    assert n_batches == len(_batches(small_setup(5)[1])) + 3
    for name in (spans.ENGINE_STAGE, spans.ENGINE_DEVICE_WAIT,
                 spans.ENGINE_FOLD):
        assert _count(lines, name) == n_batches, name
    assert _count(lines, spans.ENGINE_PUBLISH) == len(reports)
    assert _count(lines, spans.ENGINE_INGEST) == len(reports)


def test_ingest_phases_lie_inside_their_call(traced):
    _, _, lines = traced
    for evs in lines.values():
        calls = [e for e in evs if e[2] == spans.ENGINE_INGEST]
        for s, e, name in evs:
            if name in spans.ENGINE and name != spans.ENGINE_INGEST:
                assert any(a <= s and e <= b for a, b, _ in calls), name


def test_one_dispatch_span_per_counted_dispatch(traced):
    _, counters, lines = traced
    assert counters.dispatches > 0
    assert _count(lines, spans.SERVE_DISPATCH) == counters.dispatches


def test_serve_stages_lie_within_a_dispatch_on_their_line(traced):
    _, counters, lines = traced
    stages = (spans.SERVE_PROBE, spans.SERVE_ROUTE, spans.SERVE_FINISH)
    for evs in lines.values():
        dispatches = [e for e in evs if e[2] == spans.SERVE_DISPATCH]
        for s, e, name in evs:
            if name in stages:
                assert any(a <= s and e <= b for a, b, _ in dispatches), name
    assert _count(lines, spans.SERVE_FINISH) == counters.dispatches
    assert _count(lines, spans.SERVE_ROUTE) == counters.engine_dispatches


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_queue_wait_is_exact_and_counted_once_across_a_swap_retry(
        monkeypatch):
    _, work, svc = _service(seed=7, backend="numpy")
    clock = FakeClock()
    server = QueryServer(svc, ServeConfig(max_batch=8, max_delay_s=0.002),
                         clock=clock)
    live = svc.live_replica_set().primary
    route = live.engine.route_queries
    calls = []

    def torn_once(wt):
        # the first route lands under an in-place tighten: a torn result
        calls.append(1)
        out = route(wt)
        if len(calls) == 1:
            object.__setattr__(live.tree, "_desc_version",
                               getattr(live.tree, "_desc_version", 0) + 1)
        return out

    monkeypatch.setattr(live.engine, "route_queries", torn_once)
    submitted = (10.0, 10.00025, 10.001)
    for t, q in zip(submitted, work.queries):
        clock.t = t
        server.submit(q)
    clock.t = 10.005
    assert server.flush() == 1
    c = server.counters
    assert c.swap_retries == 1 and len(calls) == 2
    assert c.dispatches == 1 and c.queries_served == 3
    assert c.queue_wait_us == 5000 + 4750 + 4000
    assert server.stats()["queue_wait_ms"] == pytest.approx(13.75 / 3)

    clock.t = 20.0
    server.submit(work.queries[3])
    clock.t = 20.0035
    server.flush()
    assert server.counters.queue_wait_us == 13750 + 3500
    server.stop()
