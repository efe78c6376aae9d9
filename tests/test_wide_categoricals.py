"""Wide categorical domains: the description intersect, the greedy build
and the jax ingest step at a star schema's width.

A star schema denormalized into one table (TPC-DS ``store_sales`` with
its item, store and demographic attributes) carries about 2,000
categorical bits, with IN cuts over domains up to 1,000 codes.  The
intersect that scores the greedy builder's candidate cuts counts shared
codes with one product per column; these tests hold it to the
(descriptions, conjuncts, domain) broadcast it replaced, hold the greedy
builder to the trees it built before, and the jax ingest step at that
width to the numpy oracle, bit for bit.
"""

import glob
import hashlib
import os

import jax
import numpy as np
import pytest

from repro import spans
from repro.core import predicates as preds
from repro.core import query as qry
from repro.core.greedy import GreedyConfig, build_greedy
from repro.core.predicates import Column, CutTableBuilder, Schema
from repro.data import datagen
from repro.data import workload as wl
from repro.engine import LayoutEngine, backends, replicate_tree
from repro.kernels.ref import fused_ingest_ref
from tests.test_qdtree import random_tree

# categorical widths of a TPC-DS store_sales star: country, gender,
# weekday, month, hour, month sequence, states, class, brand, manufacturer
STAR_DOMS = (1, 2, 7, 12, 24, 60, 51, 100, 712, 1000)


def _schema(doms) -> Schema:
    cols = [Column("n0", "numeric", 500)]
    cols += [Column(f"c{i}", "categorical", d) for i, d in enumerate(doms)]
    return Schema(tuple(cols) + (Column("n1", "numeric", 90),))


def _broadcast_cat(desc_cat, wt, schema):
    """The categorical term as it was: per column, a (L, n_conj, dom)
    broadcast."""
    ok = np.ones((desc_cat.shape[0], wt.n_conjuncts), bool)
    for d in np.nonzero(schema.is_categorical)[0]:
        seg = schema.cat_segment(int(d))
        ok &= (desc_cat[:, None, seg] & wt.q_cat[None, :, seg]).any(axis=2)
    return ok


def _broadcast_intersect(desc_lo, desc_hi, desc_cat, wt, schema):
    """The conjunct intersect as it was: a (L, n_conj, ndims) box
    broadcast and the categorical term's."""
    lo = np.maximum(desc_lo[:, None, :], wt.q_lo[None, :, :])
    hi = np.minimum(desc_hi[:, None, :], wt.q_hi[None, :, :])
    ok = (lo < hi)[:, :, ~schema.is_categorical].all(axis=2)
    return ok & _broadcast_cat(desc_cat, wt, schema)


def _random_workload_tensors(rng, schema, n_conj, n_queries):
    """Conjuncts that leave each categorical column unconstrained, allow
    a random subset of it (empty and full included) or one code, with
    random numeric boxes; owners nondecreasing, some queries with none."""
    bits = schema.total_cat_bits
    q_cat = np.ones((n_conj, bits), bool)
    for c in range(n_conj):
        for d in np.nonzero(schema.is_categorical)[0]:
            seg = schema.cat_segment(int(d))
            pick = rng.integers(0, 10)
            if pick in (1, 2):
                q_cat[c, seg] = rng.random(seg.stop - seg.start) < 0.5
            elif pick == 3:
                q_cat[c, seg] = False
                q_cat[c, seg.start + rng.integers(0, seg.stop - seg.start)] = True
            elif pick == 4:
                q_cat[c, seg] = False
    doms = schema.doms
    q_lo = rng.integers(0, doms, (n_conj, len(doms)))
    q_hi = q_lo + rng.integers(0, doms, (n_conj, len(doms)))
    return qry.WorkloadTensors(
        q_lo=q_lo.astype(np.int32), q_hi=q_hi.astype(np.int32), q_cat=q_cat,
        q_adv=np.zeros((n_conj, 1), np.int8),
        conj_query=np.sort(rng.integers(0, n_queries, n_conj)).astype(np.int32),
        n_queries=n_queries,
    )


@pytest.mark.parametrize("seed", range(6))
def test_intersect_equals_the_broadcast_it_replaced(seed):
    rng = np.random.default_rng(seed)
    doms = STAR_DOMS if seed % 2 else tuple(
        int(d) for d in rng.integers(1, 1001, 8))
    schema = _schema(doms)
    n_desc, bits = 48, schema.total_cat_bits
    density = 0.05 + 0.95 * rng.random((n_desc, 1)) ** 2
    desc_cat = rng.random((n_desc, bits)) < density
    desc_cat[0] = False  # an empty block
    desc_cat[1] = True
    seg = schema.cat_segment(int(rng.choice(np.nonzero(
        schema.is_categorical)[0])))
    desc_cat[2, seg] = False  # empty in one column only
    desc_lo = rng.integers(0, schema.doms, (n_desc, len(schema.columns)))
    desc_hi = desc_lo + rng.integers(0, 60, desc_lo.shape)
    desc_lo[0] = desc_hi[0] = 0
    wt = _random_workload_tensors(rng, schema, 40, 25)

    got = qry.conjuncts_intersect(desc_lo, desc_hi, desc_cat,
                                  np.zeros((n_desc, 0, 2), bool), wt, schema)
    want = _broadcast_intersect(desc_lo, desc_hi, desc_cat, wt, schema)
    assert np.array_equal(got, want)
    assert want.any() and not want.all()
    cat = _broadcast_cat(desc_cat, wt, schema)
    assert np.array_equal(qry.cat_intersect(desc_cat, wt, schema), cat)
    assert cat.any() and not cat.all()

    by_query = np.zeros((n_desc, wt.n_queries), bool)
    np.logical_or.at(by_query, (slice(None), wt.conj_query), got)
    assert np.array_equal(qry.queries_intersect(got, wt), by_query)


def _fingerprint(tree) -> str:
    h = hashlib.sha256()
    for a in (tree.cut_id, tree.left, tree.right, tree.leaf_bid, tree.leaf_lo,
              tree.leaf_hi, tree.leaf_cat, tree.leaf_adv):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


# (blocks, depth, fingerprint) of greedy trees on 6,000 generated rows, as
# the builder gave them before its intersect counted codes by products
GOLDEN_TREES = {
    "tpch": (datagen.make_tpch_like,
             lambda s: wl.make_tpch_workload(s, 3, 1)[0],
             (214, 16, "d6a7edc2c92b2c8b")),
    "errorlog_ext": (datagen.make_errorlog_ext,
                     lambda s: wl.make_errorlog_ext_workload(s, 60, 1)[0],
                     (164, 12, "718d58d485f1bf82")),
}


@pytest.mark.parametrize("name", sorted(GOLDEN_TREES))
def test_greedy_build_gives_the_trees_it_gave_before(name):
    make, workload, want = GOLDEN_TREES[name]
    schema, rows = make(6000, 3)
    work = workload(schema)
    tree = build_greedy(rows, work, work.candidate_cuts(max_adv=4),
                        GreedyConfig(min_block=20)).freeze()
    assert (tree.n_leaves, tree.depth, _fingerprint(tree)) == want


def test_greedy_build_scores_each_node_in_a_span(tmp_path):
    schema, rows = datagen.make_tpch_like(3000, 4)
    work = wl.make_tpch_workload(schema, 2, 2)[0]
    with jax.profiler.trace(str(tmp_path)):
        tree = build_greedy(rows, work, work.candidate_cuts(max_adv=4),
                            GreedyConfig(min_block=40))
    tree = tree.freeze()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(files[0])
    scored = sum(e.name == spans.BUILD_SCORE for p in data.planes
                 if p.name == "/host:CPU" for line in p.lines
                 for e in line.events)
    internal = tree.n_nodes - tree.n_leaves
    assert internal > 0 and internal <= scored <= tree.n_nodes


def _star_tree(m=3000, splits=90):
    """A random tree over a star schema's ~2,000 categorical bits, split
    mostly by IN cuts over the wide columns."""
    schema = _schema(STAR_DOMS)
    rng = np.random.default_rng(7)
    records = np.stack([rng.integers(0, c.dom, m) for c in schema.columns],
                       axis=1).astype(np.int32)
    b = CutTableBuilder(schema)
    for c in (100, 250, 400):
        b.add_range(0, preds.OP_LT, c)
    for d in np.nonzero(schema.is_categorical)[0]:
        dom = schema.columns[d].dom
        for _ in range(min(dom - 1, 8)):
            b.add_in(int(d), rng.choice(dom, max(dom // 3, 1), replace=False))
    tree = random_tree(schema, b.build(), records, rng, max_splits=splits)
    return records, tree.freeze()


def test_jax_ingest_at_two_thousand_bits_matches_numpy():
    """At a star schema's width the jax plan keeps the path-matrix
    product and the one-hot stats product, evaluates its IN cuts by the
    one-hot product with the IN masks, names its IN cuts and bits,
    and its step equals the numpy oracle bit for bit (a padded batch
    too); the step's device ops carry the phase scopes."""
    records, tree = _star_tree()
    bits = tree.schema.total_cat_bits
    assert bits >= 1900
    eng = LayoutEngine(replicate_tree(tree), backend="jax")
    plan = backends.get_backend("jax")._ingest_plan(eng.tree, eng.plans)
    kinds = tree.cuts.kind[np.unique(tree.cut_id[tree.cut_id >= 0])]
    n_in = int((kinds == preds.KIND_IN).sum())
    assert n_in > 20
    assert plan.meta["locate"] == "paths" and plan.meta["cat_gemm"]
    assert plan.meta["in_lookup"] == "product"
    assert plan.meta["cat_bits"] == bits
    assert plan.meta["in_cuts"][0] == n_in <= plan.meta["in_cuts"][1]

    oracle = LayoutEngine(replicate_tree(tree), backend="numpy")
    for batch in (records, records[:2100]):
        bids, part = eng.fused_step(batch)
        want_bids, want = oracle.fused_step(batch)
        ref_bids, ref = fused_ingest_ref(tree, batch)
        np.testing.assert_array_equal(bids, want_bids)
        np.testing.assert_array_equal(bids, ref_bids)
        for k in ("counts", "lo", "hi", "cat", "adv"):
            np.testing.assert_array_equal(getattr(part, k), getattr(want, k))
            np.testing.assert_array_equal(getattr(part, k), getattr(ref, k))

    padded = np.zeros((4096, records.shape[1]), np.int32)
    text = plan.fn.func.lower(padded, np.ones(4096, bool),
                              **plan.fn.keywords).as_text(debug_info=True)
    for scope in spans.STEP:
        assert scope in text, scope
