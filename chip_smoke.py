"""Run the layout service end to end on one TPU and check it against numpy.

    python chip_smoke.py [--rows 6001215] [--seed 0]

The main path, through the entry points a user calls, at TPC-H SF1 size
(6,001,215 lineitem-shaped rows × 21 int32 columns, made from ``--seed``):

1. build a greedy qd-tree with ``LayoutService.build`` on a bootstrap
   sample (several hundred blocks);
2. stream the whole table through ``LayoutService.ingest`` (fused route +
   tighten, one shard) on ``backend="jax"``, then again on
   ``backend="pallas"``, each after warming its plans;
3. route the TPC-H template workload with ``route_queries`` on numpy, jax
   and pallas, and serve some of it through ``QueryServer``.

Every result is compared with the numpy reference (``FrozenQdTree.route``,
``kernels/ref.fused_ingest_ref``, ``Query.evaluate`` over the full table);
any mismatch fails the run.  The script refuses to run anywhere but on a
TPU, and it compiles every Pallas kernel for the chip (``interpret=False``).
Records/s lines are bring-up observations, not benchmarks.

The last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"
SF1_ROWS = 6_001_215  # TPC-H lineitem rows at scale factor 1


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_data(rows: int, seed: int, n_per_template: int = 5):
    """TPC-H-like table, its template workload and candidate cuts."""
    from repro.data import datagen, workload as wl

    schema, records = datagen.make_tpch_like(rows, seed=seed)
    work, _ = wl.make_tpch_workload(
        schema, n_per_template=n_per_template, seed=seed
    )
    return records, work, work.candidate_cuts(max_adv=4)


def build_service(records, work, cuts, sample_rows: int, min_block: int,
                  seed: int):
    """Greedy layout on a bootstrap sample (as ``launch/ingest.py``)."""
    from repro.service import LayoutService

    return LayoutService.build(
        records[:sample_rows], work, strategy="greedy", backend="pallas",
        cuts=cuts, min_block=min_block, seed=seed,
    )


def reference(tree, records, chunk: int = 1 << 18):
    """Numpy bids and tightening partial of the whole table, by chunks."""
    from repro.core.qdtree import IncrementalTightener
    from repro.kernels.ref import fused_ingest_ref

    bids = np.empty(records.shape[0], np.int32)
    acc = IncrementalTightener(tree)
    for s in range(0, records.shape[0], chunk):
        b, part = fused_ingest_ref(tree, records[s : s + chunk])
        bids[s : s + chunk] = b
        acc.merge(part)
    return bids, acc.as_partial()


def reference_descriptions(tree, partial) -> dict:
    """Leaf descriptions the reference partial tightens ``tree`` to."""
    from repro.core.qdtree import IncrementalTightener
    from repro.engine import replicate_tree

    replica = replicate_tree(tree)
    t = IncrementalTightener(replica)
    t.merge(partial)
    t.apply()
    return descriptions(replica)


def descriptions(tree) -> dict:
    return {
        k: getattr(tree, k).copy()
        for k in ("leaf_lo", "leaf_hi", "leaf_cat", "leaf_adv")
    }


def ingest(svc, records, backend: str, batch: int):
    """Warm the backend's ingest plans, then stream the whole table."""
    from repro.service import IngestOptions

    n = records.shape[0]
    svc.engine.warm_ingest({min(batch, n), n % batch}, backend=backend)
    report = svc.ingest(records, IngestOptions(batch=batch), backend=backend)
    return report, descriptions(svc.tree)


def check_ingest(backend, report, descs, ref_partial, ref_descs) -> None:
    if report.traces:
        raise AssertionError(f"{backend}: traces inside ingest {report.traces}")
    if not np.array_equal(report.block_sizes, ref_partial.counts):
        raise AssertionError(f"{backend}: per-block counts differ from numpy")
    for k, want in ref_descs.items():
        if not np.array_equal(descs[k], want):
            raise AssertionError(f"{backend}: tightened {k} differs from numpy")


def check_bids(engine, records, ref_bids, backend: str, batch: int) -> None:
    """Fused-ingest and routing bids of ``backend`` equal numpy's."""
    for s in range(0, records.shape[0], batch):
        want = ref_bids[s : s + batch]
        fused, _ = engine.fused_step(records[s : s + batch], backend=backend)
        routed = engine.route(records[s : s + batch], backend=backend)
        if not (np.array_equal(fused, want) and np.array_equal(routed, want)):
            raise AssertionError(f"{backend}: bids differ from numpy at {s}")


def route_all(svc, work) -> dict:
    """Per-query block lists on every backend; they must agree."""
    lists = {
        b: svc.route_queries(work, backend=b)
        for b in ("numpy", "jax", "pallas")
    }
    for b in ("jax", "pallas"):
        if any(not np.array_equal(x, y)
               for x, y in zip(lists[b], lists["numpy"])):
            raise AssertionError(f"{b}: query block lists differ from numpy")
    return lists["numpy"]


def check_sound(records, ref_bids, sizes, work, lists) -> float:
    """Every row a query selects lies in one of its blocks.

    Returns the fraction of rows the workload's block lists scan.
    """
    scanned = 0
    for i, (q, blocks) in enumerate(zip(work.queries, lists)):
        hit = np.unique(ref_bids[q.evaluate(records, work.schema)])
        if not np.isin(hit, blocks).all():
            raise AssertionError(f"query {i}: a selected row's block skipped")
        scanned += int(sizes[blocks].sum())
    return scanned / (records.shape[0] * len(lists))


def serve(svc, work, lists, n_queries: int) -> int:
    """Serve queries through ``QueryServer``; answers equal ``lists``."""
    from repro.serve import QueryServer

    server = QueryServer(svc).start()
    try:
        tickets = [server.submit(q) for q in work.queries[:n_queries]]
        results = [t.result(timeout=600) for t in tickets]
    finally:
        server.stop()
    for i, r in enumerate(results):
        if not np.array_equal(r.bids, lists[i]):
            raise AssertionError(f"served query {i} differs from routed")
    return len(results)


def pallas_interpret_flags(svc) -> set:
    """The ``interpret`` option of every cached Pallas plan."""
    return {
        k.opts[-1] for k in svc.plans.keys()
        if getattr(k, "backend", None) == "pallas"
    }


def run(rows: int, seed: int, sample_rows: int, min_block: int,
        batch: int, n_serve: int) -> dict:
    """Every phase; raises on the first check that fails."""
    from repro.engine import pad_bucket
    from repro.engine.plan import LANE

    t0 = time.perf_counter()
    records, work, cuts = make_data(rows, seed)
    log(f"table: {records.shape[0]} rows x {records.shape[1]} int32 "
        f"({records.nbytes / 1e6:.1f} MB), {len(work)} TPC-H template "
        f"queries, {cuts.n_cuts} candidate cuts "
        f"({time.perf_counter() - t0:.1f} s)")

    t0 = time.perf_counter()
    svc = build_service(records, work, cuts, sample_rows, min_block, seed)
    tree = svc.tree
    log(f"greedy layout on {sample_rows} sample rows: {tree.n_leaves} "
        f"blocks (leaf bucket {pad_bucket(tree.n_leaves, LANE)}), depth "
        f"{tree.depth} ({time.perf_counter() - t0:.1f} s)")

    t0 = time.perf_counter()
    ref_bids, ref_partial = reference(tree, records)
    ref_descs = reference_descriptions(tree, ref_partial)
    log(f"numpy reference over {rows} rows "
        f"({time.perf_counter() - t0:.1f} s)")

    rates = {}
    for backend in ("jax", "pallas"):
        report, descs = ingest(svc, records, backend, batch)
        check_ingest(backend, report, descs, ref_partial, ref_descs)
        rates[backend] = report.records_per_s
        log(f"ingest {backend}: {report.n_records} rows in "
            f"{report.n_batches} batches, {report.records_per_s:,.0f} "
            f"rec/s (bring-up observation, not a benchmark); trace delta "
            f"inside the loop {report.traces}; counts and tightened "
            f"descriptions equal numpy")
        t0 = time.perf_counter()
        check_bids(svc.engine, records, ref_bids, backend, batch)
        log(f"bids {backend}: fused ingest and route equal numpy on all "
            f"{rows} rows ({time.perf_counter() - t0:.1f} s)")

    lists = route_all(svc, work)
    frac = check_sound(records, ref_bids, ref_partial.counts, work, lists)
    log(f"route_queries: {len(lists)} block lists equal across numpy, jax "
        f"and pallas; every selected row lies in a returned block; "
        f"scanned fraction {frac:.4f}")
    served = serve(svc, work, lists, n_serve)
    log(f"QueryServer: {served} tickets answered, equal to route_queries")
    flags = pallas_interpret_flags(svc)
    log(f"pallas plans: interpret flags {sorted(flags)}")
    return {"rates": rates, "interpret": flags, "blocks": tree.n_leaves}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--rows", type=int, default=SF1_ROWS)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
        f"{enable_compile_cache()}")
    # a 100k-row bootstrap sample at min_block 100 gives several hundred
    # blocks: leaf bucket 1024, inside the fused kernel's VMEM ceiling
    out = run(args.rows, args.seed, sample_rows=100_000, min_block=100,
              batch=65_536, n_serve=16)
    if out["interpret"] != {False}:
        raise AssertionError(f"pallas plans ran interpreted: {out['interpret']}")
    stats = dev.memory_stats() or {}
    log(f"peak device memory {stats.get('peak_bytes_in_use')} bytes on "
        f"{dev.device_kind}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
