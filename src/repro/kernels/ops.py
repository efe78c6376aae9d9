"""Jit'd wrappers around the Pallas kernels.

Dense kernel operands are packed and cached by the LayoutEngine's plan
cache (``repro.engine.plan``); this module keeps the kernel-level entry
points — padding every axis to MXU-aligned tile multiples, picking
``interpret=True`` automatically off-TPU, and slicing the padding back
off.  Everything returned is numpy and bit-identical to the numpy oracles
in ``repro.core``.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import query as qry
from repro.core.qdtree import FrozenQdTree
from repro.engine.plan import LANE  # noqa: F401 — one authoritative value
from repro.engine.plan import interpret_default as _interpret_default
from repro.kernels import query_intersect as qk


def _pad_to(x: np.ndarray, axis: int, mult: int, fill=0) -> np.ndarray:
    n = x.shape[axis]
    target = max(((n + mult - 1) // mult) * mult, mult)
    if target == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - n)
    return np.pad(x, pad, constant_values=fill)


# ---------------------------------------------------------------------------
# Routing — operand packing (engine/plan.py: pack_route_constants,
# path_matrices) and plan caching live in repro.engine.
# ---------------------------------------------------------------------------
def route_records(
    tree: FrozenQdTree,
    records: np.ndarray,
    tile_m: int = 256,
    tile_l: int = LANE,
    interpret: bool | None = None,
) -> np.ndarray:
    """Record → BID via the Pallas path (paper Sec 3.1).

    Dispatches through the tree's attached LayoutEngine so the packed
    operands and the compiled kernel pair are cached per padding bucket.
    """
    from repro.engine import engine_for

    return engine_for(tree).route(
        records,
        backend="pallas",
        tile_m=tile_m,
        tile_l=tile_l,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Query ↔ block intersection (+ fused scan counting)
# ---------------------------------------------------------------------------
def query_intersect(
    tree: FrozenQdTree,
    wt: qry.WorkloadTensors,
    block_sizes: np.ndarray | None = None,
    tile_l: int = LANE,
    tile_c: int = LANE,
    interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Returns (query_hits (L, n_queries) bool, scanned_per_conj (n_conj,)).

    Mirrors ``rewards.block_query_hits`` (numpy oracle) bit-exactly.
    """
    if interpret is None:
        interpret = _interpret_default()
    schema = tree.schema
    L = tree.n_leaves
    sizes = (
        np.zeros(L, np.float32)
        if block_sizes is None
        else block_sizes.astype(np.float32)
    )
    n_adv = tree.cuts.n_adv
    a3 = max(n_adv, 1)

    leaf_lo = _pad_to(tree.leaf_lo.astype(np.float32), 0, tile_l)
    leaf_hi = _pad_to(tree.leaf_hi.astype(np.float32), 0, tile_l, fill=0)
    leaf_cat = _pad_to(tree.leaf_cat.astype(np.float32), 0, tile_l)
    advt = tree.leaf_adv[:, :, 0] if n_adv else np.zeros((L, 1), bool)
    advf = tree.leaf_adv[:, :, 1] if n_adv else np.zeros((L, 1), bool)
    leaf_advt = _pad_to(advt.astype(np.float32), 0, tile_l)
    leaf_advf = _pad_to(advf.astype(np.float32), 0, tile_l)
    leaf_size = _pad_to(sizes[None, :], 1, tile_l)

    # conjunct operands go to the kernel conjunct-major: (width, n_conj)
    nc = wt.n_conjuncts
    q_lo = _pad_to(wt.q_lo.T.astype(np.float32), 1, tile_c)
    q_hi = _pad_to(wt.q_hi.T.astype(np.float32), 1, tile_c, fill=0)
    q_cat = _pad_to(wt.q_cat.T.astype(np.float32), 1, tile_c)
    reqt = (wt.q_adv == qry.ADV_TRUE).astype(np.float32)
    reqf = (wt.q_adv == qry.ADV_FALSE).astype(np.float32)
    if reqt.shape[1] < a3:
        reqt = np.pad(reqt, ((0, 0), (0, a3 - reqt.shape[1])))
        reqf = np.pad(reqf, ((0, 0), (0, a3 - reqf.shape[1])))
    q_reqt = _pad_to(reqt.T, 1, tile_c)
    q_reqf = _pad_to(reqf.T, 1, tile_c)

    numeric_dims = tuple(
        int(i) for i in np.nonzero(~schema.is_categorical)[0]
    )
    off = schema.cat_offsets
    cat_segments = tuple(
        (int(off[d]), int(off[d]) + schema.columns[d].dom)
        for d in np.nonzero(schema.is_categorical)[0]
    )

    hits, scanned = qk.query_intersect_pallas(
        *map(
            jnp.asarray,
            (
                leaf_lo, leaf_hi, leaf_cat, leaf_advt, leaf_advf, leaf_size,
                q_lo, q_hi, q_cat, q_reqt, q_reqf,
            ),
        ),
        tile_l=tile_l,
        tile_c=tile_c,
        numeric_dims=numeric_dims,
        cat_segments=cat_segments,
        n_adv=n_adv,
        interpret=interpret,
    )
    conj_hits = np.asarray(hits)[:L, :nc] > 0.5
    scanned_per_conj = np.asarray(scanned)[0, :nc]
    q_hits = qry.queries_intersect(conj_hits, wt)
    return q_hits, scanned_per_conj
