"""Pallas kernel validation: shape/dtype sweeps + allclose vs ref oracles.

Kernels run in interpret mode on the CPU; ``tests/test_tpu_compile.py``
compiles the same pl.pallas_call/BlockSpec code for a TPU v5e.
"""

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 containers without hypothesis
    from tests._hypothesis_shim import given, settings, st

from repro.core import predicates as preds
from repro.core import query as qry
from repro.core import rewards
from repro.kernels import ops
from tests.test_qdtree import random_tree, small_setup
from tests.test_query import random_query


@pytest.mark.parametrize("tile_m", [128, 256])
@pytest.mark.parametrize("m", [64, 300, 1024])
def test_route_records_shapes(tile_m, m):
    schema, records, cuts = small_setup(seed=m + tile_m, m=max(m, 600))
    rng = np.random.default_rng(0)
    tree = random_tree(schema, cuts, records, rng)
    frozen = tree.freeze()
    recs = records[:m]
    want = frozen.route(recs)
    got = ops.route_records(frozen, recs, tile_m=tile_m, interpret=True)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", [np.int32, np.int16, np.int64])
def test_route_records_dtypes(dtype):
    schema, records, cuts = small_setup(seed=5)
    rng = np.random.default_rng(5)
    tree = random_tree(schema, cuts, records, rng)
    frozen = tree.freeze()
    recs = records[:256].astype(dtype)
    want = frozen.route(records[:256])
    got = ops.route_records(frozen, recs.astype(np.int32), interpret=True)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_route_records_property(seed):
    """Property: Pallas routing ≡ numpy oracle for random trees/records."""
    schema, records, cuts = small_setup(seed)
    rng = np.random.default_rng(seed)
    tree = random_tree(schema, cuts, records, rng)
    frozen = tree.freeze()
    recs = records[: int(rng.integers(1, 400))]
    np.testing.assert_array_equal(
        ops.route_records(frozen, recs, interpret=True), frozen.route(recs)
    )


@pytest.mark.parametrize("tile_l,tile_c", [(128, 128), (256, 128)])
def test_query_intersect_tiles(tile_l, tile_c, tpch_tree, tpch_small):
    schema, records, work, cuts = tpch_small
    frozen, bids = tpch_tree
    wt = work.tensorize(cuts)
    want = rewards.block_query_hits(frozen, wt)
    sizes = np.bincount(bids, minlength=frozen.n_leaves)
    got, scanned = ops.query_intersect(
        frozen, wt, block_sizes=sizes, tile_l=tile_l, tile_c=tile_c,
        interpret=True,
    )
    np.testing.assert_array_equal(got, want)
    # fused scan count matches the oracle's per-conjunct reduction
    conj = qry.conjuncts_intersect(
        frozen.leaf_lo, frozen.leaf_hi, frozen.leaf_cat, frozen.leaf_adv,
        wt, schema,
    )
    want_scan = (conj * sizes[:, None]).sum(axis=0)
    np.testing.assert_allclose(scanned, want_scan, rtol=1e-6)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_query_intersect_property(seed):
    schema, records, cuts = small_setup(seed)
    rng = np.random.default_rng(seed)
    tree = random_tree(schema, cuts, records, rng)
    frozen = tree.freeze()
    bids = frozen.route(records)
    frozen.tighten(records, bids)
    work = qry.Workload(
        schema, tuple(random_query(schema, rng) for _ in range(7))
    )
    wt = work.tensorize(cuts)
    want = rewards.block_query_hits(frozen, wt)
    got, _ = ops.query_intersect(frozen, wt, interpret=True)
    np.testing.assert_array_equal(got, want)


def test_eval_cuts_kernel_wide_cats():
    """IN cuts over a wide categorical bit space exercise the one-hot
    matmul path with multiple 128-lane tiles."""
    schema = preds.Schema((
        preds.Column("n", "numeric", 1000),
        preds.Column("big", "categorical", 300),
    ))
    b = preds.CutTableBuilder(schema)
    rng = np.random.default_rng(0)
    for _ in range(5):
        b.add_in(1, rng.choice(300, 40, replace=False).tolist())
    b.add_range(0, preds.OP_LT, 500)
    cuts = b.build()
    records = np.stack(
        [rng.integers(0, 1000, 512), rng.integers(0, 300, 512)], axis=1
    ).astype(np.int32)
    from repro.core.qdtree import singleton_tree

    tree = singleton_tree(schema, cuts, np.arange(512))
    M = preds.eval_cuts(records, cuts)
    tree.split(tree.root, 0, cut_matrix=M)
    frozen = tree.freeze()
    np.testing.assert_array_equal(
        ops.route_records(frozen, records, interpret=True),
        frozen.route(records),
    )
