"""Fused single-pass ingestion: bit-identity properties and lifecycle.

The fused kernels (``kernels/fused_ingest.py``, the fused jax jit, and the
numpy reference) must be indistinguishable from the legacy two-pass
route-then-tighten path — same block ids, same tightened descriptions,
same per-block counts — across every backend, batch size / padding
bucket, random tree geometry, and shard count. These tests pin that
contract property-style, plus the autotune store round trip.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 containers without hypothesis
    from tests._hypothesis_shim import given, settings, st

from repro.core import predicates as preds
from repro.core.predicates import Column, CutTableBuilder, Schema
from repro.core.qdtree import IncrementalTightener
from repro.engine import LayoutEngine, replicate_tree, sharded_ingest
from repro.engine import autotune, backends
from repro.engine import plan as planlib
from repro.engine.sharded import micro_batches
from repro.kernels.ref import fused_ingest_ref
from tests.test_qdtree import random_tree, small_setup


def _frozen(seed=0):
    schema, records, cuts = small_setup(seed)
    rng = np.random.default_rng(seed)
    tree = random_tree(schema, cuts, records, rng)
    return schema, records, cuts, tree.freeze()


def _partials_identical(a, b) -> bool:
    return (
        np.array_equal(a.counts, b.counts)
        and np.array_equal(a.lo, b.lo)
        and np.array_equal(a.hi, b.hi)
        and np.array_equal(a.cat, b.cat)
        and np.array_equal(a.adv, b.adv)
    )


def _trees_identical(a, b) -> bool:
    return (
        np.array_equal(a.leaf_lo, b.leaf_lo)
        and np.array_equal(a.leaf_hi, b.leaf_hi)
        and np.array_equal(a.leaf_cat, b.leaf_cat)
        and np.array_equal(a.leaf_adv, b.leaf_adv)
    )


# ---------------------------------------------------------------------------
# Backend bit-identity vs the numpy oracle
# ---------------------------------------------------------------------------
@settings(max_examples=24, deadline=None)
@given(st.data())
def test_fused_step_matches_oracle(data):
    """Every backend's single fused pass reproduces the numpy reference
    bit for bit — bids, counts, lo/hi, categorical and adv masks — across
    random trees (leaf counts) and batch sizes (padding buckets)."""
    backend, opts = data.draw(
        st.sampled_from(
            [("numpy", {}), ("jax", {}), ("pallas", {"interpret": True})]
        ),
        label="backend",
    )
    seed = data.draw(st.integers(min_value=0, max_value=5), label="seed")
    _, records, _, base = _frozen(seed)
    # sizes straddle the pad buckets: tiny, LANE-1/LANE/LANE+1, full
    m = data.draw(
        st.sampled_from([1, 7, 63, 64, 65, 127, 128, 129, 500]),
        label="batch",
    )
    batch = records[: min(m, records.shape[0])]
    want_bids, want_partial = fused_ingest_ref(base, batch)
    eng = LayoutEngine(replicate_tree(base), backend=backend)
    bids, partial = eng.fused_step(batch, **opts)
    np.testing.assert_array_equal(bids, want_bids)
    assert _partials_identical(partial, want_partial)


@settings(max_examples=8, deadline=None)
@given(st.data())
def test_fused_ingest_bit_identical_to_two_pass(data):
    """``ingest(fused=True)`` and the legacy two-pass path land the exact
    same tightened tree and per-block counts for any micro-batch size."""
    seed = data.draw(st.integers(min_value=0, max_value=5), label="seed")
    batch = data.draw(st.sampled_from([17, 64, 200, 500]), label="batch")
    backend = data.draw(st.sampled_from(["numpy", "jax"]), label="backend")
    _, records, _, base = _frozen(seed)

    legacy = replicate_tree(base)
    rep2 = LayoutEngine(legacy, backend=backend).ingest(
        micro_batches(records, batch), fused=False
    )
    fused = replicate_tree(base)
    repf = LayoutEngine(fused, backend=backend).ingest(
        micro_batches(records, batch), fused=True
    )
    assert not rep2.fused and repf.fused
    np.testing.assert_array_equal(repf.block_sizes, rep2.block_sizes)
    assert _trees_identical(fused, legacy)


class _ScatteredBitsSchema(Schema):
    """Categorical bit segments laid out in reverse dim order.  No real
    schema does this; the jax ingest plan's one-hot is then not at the
    schema's bit layout, so the IN masks are permuted into its order and
    the stats fall back to per-dim scatters."""

    @property
    def cat_offsets(self) -> np.ndarray:
        off = np.full(self.ndims, -1, dtype=np.int32)
        pos = 0
        for i in reversed(range(self.ndims)):
            if self.columns[i].kind == preds.KIND_CATEGORICAL:
                off[i] = pos
                pos += self.columns[i].dom
        return off


def _kind_tree(kinds, seed, splits, m=512, schema_cls=Schema):
    """Records and a random frozen tree whose cut table holds only cuts of
    ``kinds`` (``"range"``, ``"in"``, ``"adv"``)."""
    schema = schema_cls((
        Column("x", "numeric", 64),
        Column("y", "numeric", 32),
        Column("c", "categorical", 6),
        Column("d", "categorical", 9),
    ))
    rng = np.random.default_rng(seed)
    records = np.stack(
        [rng.integers(0, c.dom, m) for c in schema.columns], axis=1
    ).astype(np.int32)
    b = CutTableBuilder(schema)
    if "range" in kinds:
        for c in (8, 16, 24, 32, 48):
            b.add_range(0, preds.OP_LT, c)
        for c in (8, 16, 24):
            b.add_range(1, preds.OP_LT, c)
    if "in" in kinds:
        for vals in ([0, 1], [2], [3, 4, 5], [1, 3]):
            b.add_in(2, vals)
        for vals in ([0, 1, 2], [4], [5, 6, 7, 8], [2, 7]):
            b.add_in(3, vals)
    if "adv" in kinds:
        b.add_adv(0, preds.OP_LT, 1)
        b.add_adv(1, preds.OP_GE, 0)
    cuts = b.build()
    tree = random_tree(schema, cuts, records, rng, max_splits=splits)
    return records, tree.freeze()


def _used_kinds(tree) -> set:
    names = {preds.KIND_RANGE: "range", preds.KIND_IN: "in",
             preds.KIND_ADV: "adv"}
    used = tree.cut_id[tree.cut_id >= 0]
    return {names[int(k)] for k in tree.cuts.kind[used]}


@pytest.mark.parametrize(
    "kinds,splits,m,locate,layout",
    [
        (("range",), 12, 512, "paths", "contiguous"),
        (("in",), 12, 512, "paths", "contiguous"),
        (("range", "adv"), 12, 512, "paths", "contiguous"),
        ((), 0, 512, "paths", "contiguous"),
        (("range", "in", "adv"), 12, 300, "paths", "contiguous"),
        (("range", "in", "adv"), 12, 300, "descent", "contiguous"),
        (("in",), 12, 300, "paths", "scattered"),
        (("range", "in", "adv"), 12, 300, "paths", "scattered"),
    ],
    ids=["range_only", "in_heavy", "advanced", "single_leaf",
         "padded_rows", "past_crossover", "in_heavy_scattered_bits",
         "mixed_scattered_bits"],
)
def test_jax_ingest_locate_matches_oracle(monkeypatch, kinds, splits, m,
                                          locate, layout):
    """The jax step's leaf location — the path-matrix product, or the
    descent on a plan past ``PATHS_MAX_CELLS`` — and its IN lookup, the
    one-hot product with the IN masks, also where the schema's bit layout
    is not contiguous in dim order — reproduce the numpy reference bit
    for bit: bids and every aggregate, padded rows (a batch off its row
    bucket) included."""
    if locate == "descent":
        monkeypatch.setattr(backends, "PATHS_MAX_CELLS", 0)
    schema_cls = _ScatteredBitsSchema if layout == "scattered" else Schema
    records, tree = _kind_tree(kinds, 3, splits, schema_cls=schema_cls)
    assert _used_kinds(tree) == set(kinds)
    assert (tree.n_leaves == 1) == (splits == 0)
    batch = records[:m]
    eng = LayoutEngine(replicate_tree(tree), backend="jax")
    bids, partial = eng.fused_step(batch)
    plan = backends.get_backend("jax")._ingest_plan(eng.tree, eng.plans)
    assert plan.meta["locate"] == locate
    assert plan.meta["cat_gemm"] == (layout == "contiguous")
    assert plan.meta["in_lookup"] == "product"
    want_bids, want_partial = fused_ingest_ref(tree, batch)
    np.testing.assert_array_equal(bids, want_bids)
    assert _partials_identical(partial, want_partial)


def _cell_geometry_tree():
    """A random tree at the tpch_sf1 cell's ingest geometry: 513-1024
    leaves (leaf bucket 1024) over 64 range, 28 IN and 1 advanced cut
    (grouped cut bucket 64 + 32 + 4)."""
    schema = Schema((
        Column("x", "numeric", 1024),
        Column("y", "numeric", 1024),
        Column("c", "categorical", 16),
        Column("d", "categorical", 16),
    ))
    rng = np.random.default_rng(0)
    records = np.stack(
        [rng.integers(0, c.dom, 6000) for c in schema.columns], axis=1
    ).astype(np.int32)
    b = CutTableBuilder(schema)
    for dim in (0, 1):
        for c in range(16, 1008, 31):
            b.add_range(dim, preds.OP_LT, c)
    for dim in (2, 3):
        for v in range(14):
            b.add_in(dim, [v, (v + 5) % 16])
    b.add_adv(0, preds.OP_LT, 1)
    cuts = b.build()
    assert cuts.n_cuts == 64 + 28 + 1
    tree = random_tree(schema, cuts, records, rng, max_splits=700)
    return records, tree.freeze()


def test_jax_ingest_plan_locates_by_shape(monkeypatch):
    """At the tpch_sf1 geometry the plan takes the path-matrix product;
    a plan past the crossover keeps the descent; same-bucket batches
    reuse the compiled step (no trace)."""
    records, tree = _cell_geometry_tree()
    jax_be = backends.get_backend("jax")
    eng = LayoutEngine(replicate_tree(tree), backend="jax")
    plan = jax_be._ingest_plan(eng.tree, eng.plans)
    assert (plan.key.leaf_bucket, plan.key.cut_bucket) == (1024, 100)
    assert plan.meta["locate"] == "paths"

    eng.fused_step(records[:300])
    before = planlib.trace_counts()
    for n in (257, 400, 512):
        bids, _ = eng.fused_step(records[:n])
        np.testing.assert_array_equal(bids, tree.route(records[:n]))
    assert planlib.trace_delta(before, planlib.trace_counts()) == {}

    monkeypatch.setattr(backends, "PATHS_MAX_CELLS", 1024 * 100 - 1)
    past = LayoutEngine(replicate_tree(tree), backend="jax")
    assert jax_be._ingest_plan(past.tree, past.plans).meta["locate"] == (
        "descent"
    )


def test_fused_partial_merge_across_batches_matches_one_shot():
    """TightenPartial merge is the associative fold the sharded/streaming
    paths rely on: folding per-batch fused partials equals one fused pass
    over the whole stream."""
    _, records, _, base = _frozen(2)
    _, want = fused_ingest_ref(base, records)
    eng = LayoutEngine(replicate_tree(base), backend="numpy")
    acc = IncrementalTightener(eng.tree)
    for b in micro_batches(records, 77):
        _, part = eng.fused_step(b)
        acc.merge(part)
    assert _partials_identical(acc.as_partial(), want)


def test_fused_step_empty_batch_is_identity():
    _, records, _, base = _frozen(3)
    eng = LayoutEngine(replicate_tree(base), backend="numpy")
    bids, part = eng.fused_step(records[:0])
    assert bids.shape == (0,)
    assert int(part.counts.sum()) == 0
    # identity partial: merging it moves nothing
    acc = IncrementalTightener(eng.tree)
    acc.merge(part)
    assert int(acc.as_partial().counts.sum()) == 0


# ---------------------------------------------------------------------------
# Sharded fused ingestion
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_sharded_fused_bit_identical_to_single_stream(k):
    _, records, _, base = _frozen(5)
    oracle = replicate_tree(base)
    rep1 = LayoutEngine(oracle, backend="numpy").ingest(
        micro_batches(records, 64), fused=True
    )
    replica = replicate_tree(base)
    repk = sharded_ingest(
        LayoutEngine(replica, backend="numpy"), records, k, batch=64,
        fused=True,
    )
    np.testing.assert_array_equal(repk.block_sizes, rep1.block_sizes)
    assert _trees_identical(replica, oracle)


def test_sharded_process_executor_bit_identical():
    """``executor="process"`` spawn workers (pickled tree replica, rebuilt
    engine, worker-side warm) reproduce the thread path bit for bit."""
    _, records, _, base = _frozen(7)
    oracle = replicate_tree(base)
    LayoutEngine(oracle, backend="numpy").ingest(
        micro_batches(records, 97), fused=True
    )
    replica = replicate_tree(base)
    rep = sharded_ingest(
        LayoutEngine(replica, backend="numpy"), records, 2, batch=97,
        executor="process",
    )
    assert rep.published
    assert _trees_identical(replica, oracle)


def test_resident_process_pool_reused_across_runs():
    """Consecutive ``executor="process"`` runs share ONE resident spawn
    pool (workers pay interpreter start + jax import once, not per run);
    the pool never shrinks, and ``shutdown_process_pool`` retires it so
    the next run rebuilds lazily."""
    from repro.engine import process_pool, shutdown_process_pool

    _, records, _, base = _frozen(7)
    oracle = replicate_tree(base)
    LayoutEngine(oracle, backend="numpy").ingest(
        micro_batches(records, 97), fused=True
    )
    pool = process_pool(2)
    assert process_pool(1) is pool  # grow-only: smaller asks don't churn
    for _ in range(2):
        replica = replicate_tree(base)
        rep = sharded_ingest(
            LayoutEngine(replica, backend="numpy"), records, 2, batch=97,
            executor="process",
        )
        assert rep.published
        assert _trees_identical(replica, oracle)
        assert process_pool(1) is pool  # both runs rode the same pool
    shutdown_process_pool()
    fresh = process_pool(1)
    try:
        assert fresh is not pool
    finally:
        shutdown_process_pool()

    with pytest.raises(ValueError):
        process_pool(0)


def test_sharded_rejects_unknown_executor_string():
    _, records, _, base = _frozen(1)
    with pytest.raises(ValueError, match="executor"):
        sharded_ingest(
            LayoutEngine(replicate_tree(base), backend="numpy"),
            records, 2, executor="fork-bomb",
        )


# ---------------------------------------------------------------------------
# Autotune store
# ---------------------------------------------------------------------------
def test_autotune_store_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "REPRO_AUTOTUNE_STORE", str(tmp_path / "tiles.json")
    )
    _, _, _, base = _frozen(0)
    geom = autotune.geometry_key(base)
    assert autotune.lookup("pallas", geom) is None
    cfg = autotune.TileConfig(
        tile_m=512, tile_l=128, interpret=True, records_per_s=123.0
    )
    autotune.record("pallas", geom, cfg)
    got = autotune.lookup("pallas", geom)
    assert got is not None
    assert (got.tile_m, got.tile_l, got.interpret) == (512, 128, True)
    # unknown geometry stays a miss
    assert autotune.lookup("pallas", "c9999-l9999") is None


def test_autotune_fused_validates_and_persists(tmp_path, monkeypatch):
    """A tiny sweep: every surviving candidate is bit-validated against
    the oracle, the fallback mode is recorded (never silent), and the
    chosen tiles land in the store for the backend to pick up."""
    monkeypatch.setenv(
        "REPRO_AUTOTUNE_STORE", str(tmp_path / "tiles.json")
    )
    _, records, _, base = _frozen(4)
    tune = autotune.autotune_fused(
        base, records[:256], tile_grid=((256, 128),), reps=1
    )
    assert tune["rows"] and all(
        r["mode"] in ("compiled", "interpret", "failed")
        for r in tune["rows"]
    )
    chosen = tune["chosen"]
    assert chosen is not None
    got = autotune.lookup("pallas", tune["geometry"])
    assert got is not None and got.tile_m == chosen["tile_m"]
