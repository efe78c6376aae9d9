"""``chip_smoke.py``'s phases on the CPU, and the one-process-per-chip rule.

The script itself refuses to run off a TPU; its phases (``chip_smoke.run``)
run here at a tiny size with the Pallas kernels interpreted, so a wrong
path, argument or check fails in tier-1 rather than on the chip.
"""

from __future__ import annotations

import json
import warnings

import jax
import numpy as np
import pytest

import chip_smoke
from repro.engine import LayoutEngine, replicate_tree
from repro.engine import autotune
from repro.engine.sharded import resolve_executor
from repro.kernels import fused_ingest as fk
from repro.service import IngestOptions, LayoutService
from tests.test_fused_ingest import _frozen


def test_smoke_phases_on_cpu():
    out = chip_smoke.run(
        rows=20_000, seed=0, sample_rows=5_000, min_block=50, batch=4_096,
        n_serve=4,
    )
    assert out["interpret"] == {True}  # no chip here: every plan interprets
    assert set(out["rates"]) == {"jax", "pallas"}
    assert out["blocks"] > 1


def test_smoke_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "[chip_smoke]" not in out  # no phase ran
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_smoke_check_fails_on_wrong_descriptions():
    """A phase check raises (and so fails the script) on a mismatch."""
    _, records, _, tree = _frozen(0)
    ref_bids, ref_partial = chip_smoke.reference(tree, records, chunk=97)
    ref_descs = chip_smoke.reference_descriptions(tree, ref_partial)
    eng = LayoutEngine(replicate_tree(tree), backend="numpy")
    report = eng.ingest([records])
    descs = chip_smoke.descriptions(eng.tree)
    chip_smoke.check_ingest("numpy", report, descs, ref_partial, ref_descs)
    descs["leaf_hi"][0, 0] += 1
    with pytest.raises(AssertionError, match="leaf_hi"):
        chip_smoke.check_ingest(
            "numpy", report, descs, ref_partial, ref_descs
        )


@pytest.fixture
def on_tpu(monkeypatch):
    """JAX reports a TPU as its default backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_resolve_executor_on_tpu_stays_in_process(on_tpu, n_shards):
    assert resolve_executor(None, n_shards) == "thread"
    with pytest.raises(ValueError, match="one process"):
        resolve_executor("process", n_shards)


def test_resolve_executor_off_tpu_unchanged():
    assert resolve_executor(None, 1) == "thread"
    assert resolve_executor(None, 2) == "process"


def test_sharded_service_ingest_on_tpu_runs_in_process(on_tpu):
    _, records, _, tree = _frozen(1)
    svc = LayoutService(replicate_tree(tree), backend="numpy")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no PerformanceWarning either
        report = svc.ingest(records, IngestOptions(shards=2, batch=64))
    assert report.published
    with pytest.raises(ValueError, match="one process"):
        svc.ingest(
            records, IngestOptions(shards=2, batch=64, executor="process")
        )


def test_autotune_on_tpu_reports_compile_errors(on_tpu, tmp_path, monkeypatch):
    """Where the platform should compile, a candidate that cannot is a
    ``failed`` row: it is not rerun in the interpreter, and nothing is
    persisted.  (This CPU cannot compile Pallas, so every row fails.)"""
    store = tmp_path / "tiles.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_STORE", str(store))
    _, records, _, tree = _frozen(4)
    tune = autotune.autotune_fused(
        tree, records[:256], tile_grid=((256, 128),), reps=1
    )
    assert [r["mode"] for r in tune["rows"]] == ["failed"]
    assert "compile_error" in tune["rows"][0]
    assert tune["chosen"] is None and not store.exists()


def test_pallas_ingest_plan_refuses_leaf_bucket_above_ceiling(monkeypatch):
    _, records, _, tree = _frozen(2)
    monkeypatch.setattr(fk, "MAX_LEAF_BUCKET", 64)  # below this tree's 128
    eng = LayoutEngine(replicate_tree(tree), backend="pallas")
    with pytest.raises(ValueError, match="at most 64"):
        eng.fused_step(records[:8], interpret=True)
    # jax is there for such trees; nothing switched to it in silence
    bids, _ = LayoutEngine(replicate_tree(tree), backend="jax").fused_step(
        records[:8]
    )
    np.testing.assert_array_equal(bids, tree.route(records[:8]))
