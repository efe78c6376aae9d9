"""Numpy oracles for the fused ingestion path.

The kernel tests compare every backend against the numpy semantics:
``FrozenQdTree.route`` / ``query.conjuncts_intersect`` for routing and
intersection, and here ``fused_ingest_ref`` for the fused single-pass
ingestion path: route via the numpy descent, tighten via the legacy
``IncrementalTightener`` arithmetic, packaged as the same
``(bids, TightenPartial)`` pair every fused backend returns.
``partial_from_fused`` converts a device backend's f32 aggregates into
that exchange format.
"""

from __future__ import annotations

import numpy as np


def fused_ingest_ref(tree, records):
    """Numpy bit-identity oracle: one batch routed + reduced per leaf.

    Exactly the legacy two-pass arithmetic (``FrozenQdTree.route`` then
    ``IncrementalTightener.update``), returned in the fused-path shape:
    ``(bids int32, TightenPartial)``.  Every fused backend must reproduce
    this bit-for-bit.
    """
    from repro.core.qdtree import IncrementalTightener

    bids = tree.route(records)
    t = IncrementalTightener(tree)
    t.update(records, bids)
    return bids, t.as_partial()


def partial_from_fused(tree, counts, lo, hi, cat, advt, advf):
    """Convert fused-kernel f32 aggregates (already sliced to the tree's
    ``n_leaves``) into the numpy tightener's exchange format.

    Dictionary codes are < 2**24, so the f32 → int64 narrowing is exact;
    empty leaves get the tightener's int64 identity elements and ``hi``
    becomes exclusive (max + 1) — bit-identical to
    ``IncrementalTightener.update`` over the same records.
    """
    from repro.core.qdtree import TightenPartial

    i64 = np.iinfo(np.int64)
    counts = np.asarray(counts).astype(np.int64)
    ne = counts > 0
    lo64 = np.where(
        ne[:, None], np.asarray(lo).astype(np.int64), i64.max
    )
    hi64 = np.where(
        ne[:, None], np.asarray(hi).astype(np.int64) + 1, i64.min
    )
    pcat = np.zeros_like(tree.leaf_cat)
    nb = min(pcat.shape[1], cat.shape[1])
    pcat[:, :nb] = np.asarray(cat[:, :nb]) > 0.5
    pcat &= ne[:, None]
    padv = np.zeros_like(tree.leaf_adv)
    na = tree.cuts.n_adv
    if na:
        padv[:, :, 0] = np.asarray(advt[:, :na]) > 0.5
        padv[:, :, 1] = np.asarray(advf[:, :na]) > 0.5
        padv &= ne[:, None, None]
    return TightenPartial(
        counts=counts, lo=lo64, hi=hi64, cat=pcat, adv=padv
    )
