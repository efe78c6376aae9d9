"""Tile-shape autotuner for the fused Pallas ingestion kernel.

The fused kernel (``kernels/fused_ingest.py``) is tiled over
``tile_m`` record rows × ``tile_l`` leaf columns; the right shapes depend
on the tree geometry (cut/leaf buckets set the operand matrices) and on
whether the platform compiles Pallas at all (TPU) or runs it in interpret
mode (CPU/GPU dev boxes).  This module owns that decision:

* :func:`autotune_fused` sweeps a tile grid against a sample batch in the
  platform's one mode (compiled on a TPU, interpret elsewhere), validates
  every candidate bit-identically against the numpy oracle
  (``kernels/ref.fused_ingest_ref``), reports a candidate that fails to
  compile as a ``failed`` row — it is never retried on a slower path —
  then persists the fastest valid config with the mode it ran in.
* :func:`lookup` / :func:`record` read/write the persisted store, keyed by
  ``(backend, geometry-bucket)``; ``PallasBackend.fused_outputs`` consults
  it when the caller does not pin tiles explicitly.

The store is a plain JSON file (``results/autotune_tiles.json`` by
default, override with ``REPRO_AUTOTUNE_STORE``) so tuned tiles survive
across processes and land in benchmark artifacts.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time
from typing import Optional

import numpy as np

from repro.engine.plan import LANE, interpret_default, pad_bucket

_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_STORE = _ROOT / "results" / "autotune_tiles.json"

# default sweep: record-tile × leaf-tile candidates (leaf tiles are LANE
# multiples; the plan clamps tile_l to the leaf bucket)
DEFAULT_TILE_GRID = (
    (256, LANE),
    (256, 2 * LANE),
    (512, LANE),
    (512, 2 * LANE),
    (1024, LANE),
)


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One persisted tuning decision for a (backend, geometry) bucket."""

    tile_m: int
    tile_l: int
    interpret: bool  # the mode the tiles were timed in (lookups match it)
    records_per_s: float = 0.0
    source: str = "autotune"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "TileConfig":
        return TileConfig(
            tile_m=int(d["tile_m"]),
            tile_l=int(d["tile_l"]),
            interpret=bool(d["interpret"]),
            records_per_s=float(d.get("records_per_s", 0.0)),
            source=str(d.get("source", "autotune")),
        )


def geometry_key(tree) -> str:
    """Padding-bucket geometry signature: trees in the same cut/leaf
    buckets share operand shapes, hence tile behavior."""
    cut_bucket = pad_bucket(tree.cuts.n_cuts, LANE)
    leaf_bucket = pad_bucket(tree.n_leaves, LANE)
    return f"c{cut_bucket}-l{leaf_bucket}"


def store_path() -> pathlib.Path:
    env = os.environ.get("REPRO_AUTOTUNE_STORE")
    return pathlib.Path(env) if env else DEFAULT_STORE


def _load_store() -> dict:
    path = store_path()
    if path.exists():
        try:
            return json.loads(path.read_text())
        except (json.JSONDecodeError, OSError):
            return {}
    return {}


def lookup(backend: str, geom: str) -> Optional[TileConfig]:
    entry = _load_store().get(f"{backend}:{geom}")
    return TileConfig.from_dict(entry) if entry else None


def record(backend: str, geom: str, cfg: TileConfig) -> None:
    store = _load_store()
    store[f"{backend}:{geom}"] = cfg.to_dict()
    path = store_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(store, indent=2, sort_keys=True) + "\n")


def _partials_identical(a, b) -> bool:
    return (
        bool(np.array_equal(a.counts, b.counts))
        and bool(np.array_equal(a.lo, b.lo))
        and bool(np.array_equal(a.hi, b.hi))
        and bool(np.array_equal(a.cat, b.cat))
        and bool(np.array_equal(a.adv, b.adv))
    )


def autotune_fused(
    tree,
    records: np.ndarray,
    tile_grid=DEFAULT_TILE_GRID,
    reps: int = 3,
    persist: bool = True,
) -> dict:
    """Sweep fused-ingest tile shapes on the pallas backend; persist the win.

    Every candidate runs in the platform's one mode (``interpret_default``:
    compiled on a TPU, interpreted elsewhere) and is validated
    bit-identically against the numpy oracle before it may win.  A
    candidate that raises is a ``failed`` row carrying its error.
    """
    from repro.engine.engine import engine_for
    from repro.kernels.ref import fused_ingest_ref

    engine = engine_for(tree)
    oracle_bids, oracle_partial = fused_ingest_ref(tree, records)
    geom = geometry_key(tree)
    interpret = interpret_default()
    mode = "interpret" if interpret else "compiled"
    rows = []
    for tile_m, tile_l in tile_grid:
        row: dict = {"tile_m": int(tile_m), "tile_l": int(tile_l)}

        def step():
            return engine.fused_step(
                records, backend="pallas", tile_m=tile_m, tile_l=tile_l,
                interpret=interpret,
            )

        try:
            bids, partial = step()
        except Exception as exc:  # reported, never retried elsewhere
            row["mode"] = "failed"
            row["valid"] = False
            row["compile_error"] = f"{type(exc).__name__}: {exc}"[:200]
            rows.append(row)
            continue
        row["mode"] = mode
        row["valid"] = bool(
            np.array_equal(bids, oracle_bids)
        ) and _partials_identical(partial, oracle_partial)
        if row["valid"]:
            t0 = time.perf_counter()
            for _ in range(reps):
                step()
            dt = (time.perf_counter() - t0) / reps
            row["records_per_s"] = float(records.shape[0] / dt)
        rows.append(row)
    valid = [r for r in rows if r.get("valid")]
    chosen = None
    if valid:
        best = max(valid, key=lambda r: r["records_per_s"])
        chosen = TileConfig(
            tile_m=best["tile_m"],
            tile_l=best["tile_l"],
            interpret=interpret,
            records_per_s=best["records_per_s"],
        )
        if persist:
            record("pallas", geom, chosen)
    return {
        "geometry": geom,
        "rows": rows,
        "chosen": chosen.to_dict() if chosen else None,
        "compiled_available": any(r["mode"] == "compiled" for r in rows),
    }


__all__ = [
    "DEFAULT_TILE_GRID",
    "TileConfig",
    "autotune_fused",
    "geometry_key",
    "lookup",
    "record",
    "store_path",
]
