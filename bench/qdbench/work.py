"""Peaks of the chips the benchmark knows, and the work its device steps
must do, counted from shapes alone.

The peaks live in ``bench/peaks.json``, keyed by JAX's ``device_kind``.
A device that is not in the table is an error: a roofline share is
never read against a guessed peak.

The count is of what the algorithm needs, whichever backend runs it:
the ingest step reads each row's ``cols`` 4-byte codes from HBM once;
per row it compares one cut per tree level on the way down, and folds
every column into its block's minimum and maximum.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def ingest_step(rows: int, cols: int, depth: int) -> dict:
    return {"bytes": 4 * rows * cols, "ops": rows * (depth + 2 * cols)}


def roofline_s(work: dict, peak: dict) -> tuple[float, str]:
    """Least time the chip could take and which of its peaks bounds it
    (the vector units' operations are counted against the bf16 peak)."""
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = work["ops"] / peak["bf16_flops_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "compute")
