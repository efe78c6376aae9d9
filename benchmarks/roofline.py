"""Fused-ingest roofline: one-pass vs two-pass, tile autotuning, modes.

Ingestion is I/O-bound (the paper's premise — data skipping pays because
scans are bandwidth-limited), so the natural roofline axis is *record
touches*: the legacy hot path reads every record twice (route, then
tighten), the fused kernels (``kernels/fused_ingest.py``) exactly once.
This benchmark measures both paths through ``LayoutEngine.ingest`` on the
same stream and reports

  * two-pass vs fused wall/throughput on the jax backend (acceptance:
    fused ≥ 1.5× at bench scale, zero warm retraces on both),
  * bit-identity of every fused backend (numpy / jax / pallas-interpret)
    against the numpy oracle ``kernels/ref.fused_ingest_ref``,
  * the tile autotuner sweep (``engine/autotune.autotune_fused``): each
    candidate runs in the platform's one mode — ``compiled`` on a TPU,
    ``interpret`` elsewhere; a compile error is a ``failed`` row — and
    the chosen tiles are persisted per (backend, geometry bucket),
  * the record-touch counters and effective bytes/s per path (the
    deterministic roofline terms; timings vary, counters must not).

Results land in ``BENCH_fused_ingest.json`` (``_smoke`` suffix on CI).

    PYTHONPATH=src python -m benchmarks.roofline            # bench scale
    PYTHONPATH=src python -m benchmarks.roofline --smoke    # CI tiny
"""

from __future__ import annotations

import argparse
import json
import pathlib

import numpy as np

from benchmarks import common
from repro.engine import LayoutEngine, replicate_tree
from repro.engine import autotune as autotune_mod
from repro.engine import plan as planlib
from repro.engine.sharded import micro_batches, warm_sizes
from repro.kernels.ref import fused_ingest_ref

OUT = pathlib.Path(__file__).resolve().parent.parent / (
    "BENCH_fused_ingest.json"
)


def _partials_identical(a, b) -> bool:
    return (
        bool(np.array_equal(a.counts, b.counts))
        and bool(np.array_equal(a.lo, b.lo))
        and bool(np.array_equal(a.hi, b.hi))
        and bool(np.array_equal(a.cat, b.cat))
        and bool(np.array_equal(a.adv, b.adv))
    )


def _timed_ingest(base, records, batch, fused: bool, backend: str):
    """One warmed ingest run on a private replica; returns (report, tree)."""
    replica = replicate_tree(base)
    eng = LayoutEngine(replica, backend=backend)
    sizes = warm_sizes(records.shape[0], 1, batch)
    if fused:
        eng.warm_ingest(sizes)
    else:
        d = records.shape[1]
        for s in sizes:
            eng.route(np.zeros((s, d), np.int32))
    rep = eng.ingest(micro_batches(records, batch), fused=fused)
    assert not rep.traces, (
        f"warmed {'fused' if fused else 'two-pass'} ingest retraced: "
        f"{rep.traces}"
    )
    return rep, replica


def run(scale: float = 0.5, seed: int = 0, smoke: bool = False,
        batch: int = 4096) -> dict:
    from repro.core import greedy

    if smoke:
        scale, batch = 0.05, 256
    schema, records, work, labels, cuts, min_block = common.load_workload(
        "tpch", scale, seed
    )
    tree = greedy.build_greedy(
        records, work, cuts, greedy.GreedyConfig(min_block=min_block)
    )
    base = tree.freeze()
    n = int(records.shape[0])
    d = int(records.shape[1])
    print(
        f"[roofline] {n} records × {d} dims over {base.n_leaves} blocks, "
        f"batch={batch}"
    )

    # -- two-pass vs fused on the jax backend --------------------------------
    rep2, tree2 = _timed_ingest(base, records, batch, fused=False,
                                backend="jax")
    repf, treef = _timed_ingest(base, records, batch, fused=True,
                                backend="jax")
    fused_matches = (
        np.array_equal(treef.leaf_lo, tree2.leaf_lo)
        and np.array_equal(treef.leaf_hi, tree2.leaf_hi)
        and np.array_equal(treef.leaf_cat, tree2.leaf_cat)
        and np.array_equal(treef.leaf_adv, tree2.leaf_adv)
        and np.array_equal(repf.block_sizes, rep2.block_sizes)
    )
    assert fused_matches, "fused ingest diverged from two-pass"
    speedup = repf.records_per_s / rep2.records_per_s
    print(
        f"[roofline] jax two-pass {rep2.records_per_s:>12,.0f} rec/s | "
        f"fused {repf.records_per_s:>12,.0f} rec/s | {speedup:.2f}x"
    )

    # -- bit-identity of every fused backend vs the numpy oracle -------------
    m_sample = min(4096 if not smoke else 1024, n)
    sample = records[:m_sample]
    oracle_bids, oracle_partial = fused_ingest_ref(base, sample)
    eng = LayoutEngine(base)
    bit_identical = {}
    for backend, label, kw in (
        ("numpy", "numpy", {}),
        ("jax", "jax", {}),
        ("pallas", "pallas_interpret", {"interpret": True}),
    ):
        bids, partial = eng.fused_step(sample, backend=backend, **kw)
        bit_identical[label] = bool(
            np.array_equal(bids, oracle_bids)
        ) and _partials_identical(partial, oracle_partial)
        assert bit_identical[label], f"{label}: fused != numpy oracle"
    print(f"[roofline] bit-identity: {bit_identical}")

    # -- tile autotune sweep (the platform's mode; failures are rows) --------
    grid = ((256, 128), (512, 128)) if smoke else None
    tune = autotune_mod.autotune_fused(
        base,
        records[: min(2048 if smoke else 16384, n)],
        **({"tile_grid": grid} if grid else {}),
        reps=1 if smoke else 3,
        persist=not smoke,  # CI smoke leaves the tracked store alone
    )
    modes = {r["mode"] for r in tune["rows"]}
    print(
        f"[roofline] autotune geometry={tune['geometry']} "
        f"modes={sorted(modes)} chosen={tune['chosen']}"
    )

    # -- roofline terms: deterministic counters + effective bytes/s ----------
    touches_two_pass = 2 * n
    touches_fused = n
    bytes_per_touch = d * 4  # f32/int32 dictionary codes
    results = {
        "n_records": n,
        "n_dims": d,
        "n_blocks": int(base.n_leaves),
        "batch": batch,
        "smoke": smoke,
        "two_pass": {
            "backend": "jax",
            "records_per_s": rep2.records_per_s,
            "wall_s": rep2.wall_s,
            "warm_retraces": sum(rep2.traces.values()),
            "effective_bytes_per_s": (
                touches_two_pass * bytes_per_touch / rep2.wall_s
                if rep2.wall_s else 0.0
            ),
        },
        "fused": {
            "backend": "jax",
            "records_per_s": repf.records_per_s,
            "wall_s": repf.wall_s,
            "warm_retraces": sum(repf.traces.values()),
            "effective_bytes_per_s": (
                touches_fused * bytes_per_touch / repf.wall_s
                if repf.wall_s else 0.0
            ),
        },
        "speedup_fused_vs_two_pass": float(speedup),
        "record_touches": {
            "two_pass": touches_two_pass,
            "fused": touches_fused,
        },
        "bit_identical": bit_identical,
        "autotune": {
            "geometry": tune["geometry"],
            "rows": tune["rows"],
            "chosen": tune["chosen"],
            "compiled_available": tune["compiled_available"],
        },
        "assertions": {
            "fused_matches_two_pass": bool(fused_matches),
            "zero_warm_retraces": not rep2.traces and not repf.traces,
            "bit_identical_all_backends": all(bit_identical.values()),
            "fused_speedup_ge_1_5": bool(speedup >= 1.5),
        },
    }
    if not smoke:
        # acceptance at bench scale; smoke shapes are compile-dominated
        assert speedup >= 1.5, (
            f"fused ingest {speedup:.2f}x two-pass, expected >= 1.5x"
        )
    # smoke runs (CI) must not clobber the committed bench-scale numbers
    out = OUT.with_stem(OUT.stem + "_smoke") if smoke else OUT
    out.write_text(json.dumps(results, indent=2))
    print(f"[roofline] wrote {out}")
    # keep global trace counters visible for debugging CI failures
    results["traces"] = planlib.trace_counts()
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (same bit-identity assertions)")
    args = ap.parse_args()
    run(scale=args.scale, seed=args.seed, smoke=args.smoke,
        batch=args.batch)
