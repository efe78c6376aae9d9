"""Serving tier: semantic-cache acceptance + closed-loop throughput.

Two phases over one LayoutService, mirroring how the serving tier runs:

**Phase 1 — deterministic (the pinnable counters).**  Synchronous
``QueryServer.serve_batch`` rounds of a Zipf-repeated query mix on the
calling thread (no dispatcher scheduling in the numbers), with a hot swap
to a differently-built tree mid-run.  Asserts the acceptance criteria and
records them in ``BENCH_serving.json``:

  * every response — cache hit or engine miss — is BIT-IDENTICAL to
    routing the same query directly on that generation's engine,
  * ZERO stale-generation responses across the mid-run hot swap,
  * ZERO warm-plan retraces outside the swap warm-up,
  * the cache-hit path is ≥ HIT_GATE× faster than dispatching the
    same batch to the engine (≥5× bench, ≥2× noise-tolerant smoke).

**Phase 2 — closed loop (timings, never pinned).**  N client threads
submit through the async dispatcher (admission → coalesce → cache →
engine) while the main thread hot-swaps the layout under live traffic;
reports achieved qps and the mean queue wait (submission to dispatch),
and re-asserts zero staleness and bit-identity under concurrency.

    PYTHONPATH=src python -m benchmarks.serving            # bench scale
    PYTHONPATH=src python -m benchmarks.serving --smoke    # CI tiny
"""

from __future__ import annotations

import argparse
import json
import pathlib
import threading
import time

import numpy as np

from repro.core import query as qry
from repro.data import datagen
from repro.engine import trace_counts
from repro.engine.plan import trace_delta
from repro.serve import QueryServer, ServeConfig
from repro.service import LayoutService, build_layout

from benchmarks.drift_rebuild import range_workload

OUT = pathlib.Path(__file__).resolve().parent.parent / "BENCH_serving.json"

ZIPF_S = 1.1
ROUND_QUERIES = 64  # == ServeConfig.max_batch: one dispatch per round


def zipf_probs(n: int, s: float = ZIPF_S) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** -s
    return p / p.sum()


def sample_round(rng, work: qry.Workload, p: np.ndarray) -> list[qry.Query]:
    idx = rng.choice(len(work), size=ROUND_QUERIES, p=p)
    return [work.queries[int(i)] for i in idx]


def verify_bit_identity(svc: LayoutService, pairs) -> bool:
    """Every response == routing that query directly on its generation's
    engine (retained versions keep superseded generations checkable)."""
    for q, res in pairs:
        direct = svc.version(res.generation).engine.route_query(q)
        if not np.array_equal(res.bids, direct):
            return False
    return True


def run(smoke: bool = False, backend: str = "jax", seed: int = 0) -> dict:
    if smoke:
        rows, min_block, templates, rounds = 8_000, 150, 24, 10
        clients, per_client, hit_gate = 2, 80, 2.0
        timing_reps = 30
    else:
        rows, min_block, templates, rounds = 48_000, 250, 64, 40
        clients, per_client, hit_gate = 4, 400, 5.0
        timing_reps = 100

    schema, records = datagen.make_tpch_like(rows, seed=seed)
    work = range_workload(schema, dim=0, n_queries=templates, frac=0.04,
                          seed=seed + 1)
    svc = LayoutService.build(
        records, work, strategy="greedy", backend=backend,
        min_block=min_block, seed=seed,
    )
    print(
        f"[serving] {rows} rows, {svc.tree.n_leaves} blocks, "
        f"{templates} query templates (zipf s={ZIPF_S}), backend={backend}"
    )
    config = ServeConfig(
        max_batch=ROUND_QUERIES, max_delay_s=0.001, cache_capacity=4096
    )

    # ---- phase 1: deterministic sync rounds with a mid-run hot swap ----
    tracker = svc.workload_tracker()
    server = QueryServer(svc, config, tracker=tracker)  # sync: no start()
    server.warm(work)
    rng = np.random.default_rng(seed + 2)
    p = zipf_probs(templates)
    pairs: list = []
    retraces_outside_swap: dict = {}
    swap_round = rounds // 2
    swap_generation = None
    t0 = trace_counts()
    for r in range(rounds):
        if r == swap_round:
            # a *different* tree (coarser blocks): the generation epoch
            # bump must invalidate every cached entry; compiling the
            # incoming generation's plans is swap cost, excluded exactly
            # as the other benchmarks exclude it
            candidate = build_layout(
                records, work, strategy="greedy",
                min_block=min_block * 2, seed=seed + 9,
            )
            swap_generation = svc.swap(candidate)
            server.warm(work)
            t0 = trace_counts()
        queries = sample_round(rng, work, p)
        results = server.serve_batch(queries)
        pairs += list(zip(queries, results))
        delta = trace_delta(t0, trace_counts())
        if delta:
            retraces_outside_swap[r] = delta
        t0 = trace_counts()

    det = server.stats()  # pinned counters: snapshot BEFORE timing reps
    hit_rate = det["cache"]["hit_rate"]
    bit_identical = verify_bit_identity(svc, pairs)
    print(
        f"[serving] phase 1: {det['counters']['queries_served']} queries "
        f"in {rounds} rounds, hit rate {hit_rate:.3f}, "
        f"{det['counters']['engine_dispatches']} engine dispatches, "
        f"swap at round {swap_round} -> gen {swap_generation}, "
        f"bit-identical {bit_identical}, "
        f"stale {det['counters']['stale_responses']}"
    )

    # ---- hit path vs engine dispatch (same batch, both warm) ----
    hot = sample_round(rng, work, p)
    server.serve_batch(hot)  # populate: every signature now cached
    hit_s = min(
        _timed(lambda: server.serve_batch(hot)) for _ in range(timing_reps)
    )
    live = svc.live_version()

    def engine_dispatch():
        # a fresh Workload per dispatch, exactly as the serving miss path
        # constructs one — reusing a single workload object here would let
        # per-object tensor state (wt-LRU entries, folded conjuncts) warm
        # across reps and understate what a real uncached dispatch costs
        wl = qry.Workload(work.schema, tuple(hot))
        return live.engine.route_queries(wl.tensorize(live.tree.cuts))

    engine_dispatch()  # compile/warm this geometry's plans once
    eng_s = min(_timed(engine_dispatch) for _ in range(timing_reps))
    hit_speedup = eng_s / hit_s if hit_s else float("inf")
    server.stop()
    print(
        f"[serving] hit path {hit_s * 1e3:.3f}ms vs engine dispatch "
        f"{eng_s * 1e3:.3f}ms per {ROUND_QUERIES}-query batch -> "
        f"{hit_speedup:.1f}x (gate {hit_gate}x)"
    )

    # ---- phase 2: threaded closed loop under a live hot swap ----
    server2 = QueryServer(svc, config, tracker=svc.workload_tracker())
    server2.start()
    server2.warm(work)
    cl_pairs: list = []
    cl_lock = threading.Lock()
    errors: list = []

    def client(tid: int) -> None:
        crng = np.random.default_rng(seed + 100 + tid)
        mine = []
        try:
            for _ in range(per_client):
                q = work.queries[int(crng.choice(templates, p=p))]
                res = server2.serve(q, tenant=f"t{tid}", timeout=60.0)
                mine.append((q, res))
        except BaseException as e:  # surfaced after join
            errors.append(e)
        with cl_lock:
            cl_pairs.extend(mine)

    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(clients)
    ]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    # hot-swap under live traffic: back to the fine-grained layout
    time.sleep(0.05 if smoke else 0.2)
    candidate2 = build_layout(
        records, work, strategy="greedy", min_block=min_block,
        seed=seed + 17,
    )
    live_swap_gen = svc.swap(candidate2)
    server2.warm(work)
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    server2.stop()
    if errors:
        raise errors[0]
    cl = server2.stats()
    cl_bit_identical = verify_bit_identity(svc, cl_pairs)
    qps = len(cl_pairs) / wall if wall else 0.0
    print(
        f"[serving] phase 2: {len(cl_pairs)} queries from {clients} "
        f"closed-loop clients in {wall:.2f}s -> {qps:,.0f} qps, "
        f"mean queue wait {cl['queue_wait_ms']:.3f}ms, hit rate "
        f"{cl['cache']['hit_rate']:.3f}, swap under traffic -> gen "
        f"{live_swap_gen}, stale {cl['counters']['stale_responses']}, "
        f"bit-identical {cl_bit_identical}"
    )

    zero_stale = (
        det["counters"]["stale_responses"] == 0
        and cl["counters"]["stale_responses"] == 0
    )
    results_doc = {
        "n_records": rows,
        "n_blocks": int(svc.version(1).tree.n_leaves),
        "templates": templates,
        "zipf_s": ZIPF_S,
        "round_queries": ROUND_QUERIES,
        "backend": backend,
        "smoke": smoke,
        "deterministic": {
            "rounds": rounds,
            "swap_round": swap_round,
            "swap_generation": swap_generation,
            "queries_served": det["counters"]["queries_served"],
            "queries_cached": det["counters"]["queries_cached"],
            "queries_routed": det["counters"]["queries_routed"],
            "dispatches": det["counters"]["dispatches"],
            "engine_dispatches": det["counters"]["engine_dispatches"],
            "hits": det["cache"]["hits"],
            "misses": det["cache"]["misses"],
            "insertions": det["cache"]["insertions"],
            "invalidated": det["cache"]["invalidated"],
            "stale_puts": det["cache"]["stale_puts"],
            "stale_responses": det["counters"]["stale_responses"],
            "hit_rate": hit_rate,
            "bit_identical": bit_identical,
            "retraces_outside_swap": retraces_outside_swap,
        },
        "hit_path": {
            "hit_ms": hit_s * 1e3,
            "engine_dispatch_ms": eng_s * 1e3,
            "speedup": hit_speedup,
            "gate": hit_gate,
        },
        "closed_loop": {
            "clients": clients,
            "per_client": per_client,
            "queries": len(cl_pairs),
            "qps": qps,
            "queue_wait_ms": cl["queue_wait_ms"],
            "hit_rate": cl["cache"]["hit_rate"],
            "stale_responses": cl["counters"]["stale_responses"],
            "swap_generation": live_swap_gen,
            "bit_identical": cl_bit_identical,
            "admission": cl["admission"],
        },
        "assertions": {
            "bit_identical_hits": bit_identical,
            "bit_identical_closed_loop": cl_bit_identical,
            "zero_stale_responses": zero_stale,
            "zero_retraces_outside_swap": not retraces_outside_swap,
            "hit_speedup_ok": hit_speedup >= hit_gate,
            "hit_gate": hit_gate,
        },
    }
    assert bit_identical, "a served response diverged from engine routing"
    assert cl_bit_identical, (
        "a closed-loop response diverged from engine routing"
    )
    assert zero_stale, (
        f"stale-generation responses served: det="
        f"{det['counters']['stale_responses']} "
        f"cl={cl['counters']['stale_responses']}"
    )
    assert not retraces_outside_swap, (
        f"serving retraced warm plans: {retraces_outside_swap}"
    )
    assert hit_speedup >= hit_gate, (
        f"cache hit path only {hit_speedup:.2f}x faster than an engine "
        f"dispatch (gate {hit_gate}x)"
    )
    # smoke runs (CI) must not clobber the committed bench-scale numbers
    out = OUT.with_stem(OUT.stem + "_smoke") if smoke else OUT
    out.write_text(json.dumps(results_doc, indent=2))
    print(f"[serving] wrote {out}")
    return results_doc


def _timed(fn) -> float:
    t = time.perf_counter()
    fn()
    return time.perf_counter() - t


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="jax",
                    choices=("numpy", "jax", "pallas"))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (same assertions, 2x gate)")
    args = ap.parse_args()
    run(smoke=args.smoke, backend=args.backend, seed=args.seed)
