"""Batched data routing through a frozen qd-tree (paper Sec 3.1).

Three interchangeable backends, all bit-identical on records whose
categorical codes lie inside their column's domain (the schema forbids
any other; a code outside it may read differently on each backend):

* ``FrozenQdTree.route``      — numpy oracle (core/qdtree.py)
* engine "jax" backend        — jitted jnp level-synchronous descent
* ``kernels.ops.route_records`` — Pallas TPU kernel (one-hot matmul descent)

Backend dispatch, operand packing, and compilation caching live in the
:mod:`repro.engine` subsystem — ``route`` below is a thin compatibility
shim over the tree's attached :class:`~repro.engine.LayoutEngine`, whose
plan cache pads batch/tree sizes to power-of-two buckets so online
ingestion of varying shapes reuses jit/Pallas compilations.
``eval_cuts_jax``/``cut_arrays`` remain here as the jnp predicate
evaluation the engine's "jax" backend builds on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import predicates as preds
from repro.core.qdtree import FrozenQdTree


def cut_arrays(cuts: preds.CutTable) -> dict:
    """Pack the cut table for jnp evaluation."""
    adv = np.array(
        [(a.col_a, a.op, a.col_b) for a in cuts.adv], np.int32
    ).reshape(-1, 3)
    return {
        "kind": jnp.asarray(cuts.kind),
        "dim": jnp.asarray(np.maximum(cuts.dim, 0)),
        "cutpoint": jnp.asarray(cuts.cutpoint),
        "in_mask": jnp.asarray(cuts.in_mask),
        "adv_id": jnp.asarray(np.maximum(cuts.adv_id, 0)),
        "adv": jnp.asarray(adv),
        "cat_offset": jnp.asarray(np.maximum(cuts.schema.cat_offsets, 0)),
    }


def eval_cuts_jax(records: jnp.ndarray, ca: dict) -> jnp.ndarray:
    """(m, n_cuts) bool predicate matrix — jnp mirror of preds.eval_cuts."""
    vals = records[:, ca["dim"]]  # (m, n_cuts) gathered column values
    rng = vals < ca["cutpoint"][None, :]
    # IN: bit lookup at (cut, value + dim offset)
    bitpos = vals + ca["cat_offset"][ca["dim"]][None, :]
    bitpos = jnp.clip(bitpos, 0, ca["in_mask"].shape[1] - 1)
    inm = _in_lookup(ca["in_mask"], bitpos)
    # advanced predicates
    if ca["adv"].shape[0] > 0:
        va = records[:, ca["adv"][:, 0]]
        vb = records[:, ca["adv"][:, 2]]
        op = ca["adv"][:, 1][None, :]
        advt = jnp.select(
            [op == 0, op == 1, op == 2, op == 3, op == 4, op == 5],
            [va < vb, va <= vb, va > vb, va >= vb, va == vb, va != vb],
        )
        advm = advt[:, ca["adv_id"]]
    else:
        advm = jnp.zeros_like(rng)
    k = ca["kind"][None, :]
    return jnp.where(
        k == preds.KIND_RANGE, rng, jnp.where(k == preds.KIND_IN, inm, advm)
    )


def _in_lookup(in_mask: jnp.ndarray, bitpos: jnp.ndarray) -> jnp.ndarray:
    """in_mask[c, bitpos[m, c]] without materializing (m, n_cuts, bits)."""
    # vmap over the cut axis: each cut has its own mask row + position column.
    def per_cut(mask_row, pos_col):
        return mask_row[pos_col]

    return jax.vmap(per_cut, in_axes=(0, 1), out_axes=1)(in_mask, bitpos)


def route(
    tree: FrozenQdTree, records: np.ndarray, backend: str = "jax"
) -> np.ndarray:
    """Route ``records`` on a registered backend (compatibility shim).

    Delegates to the tree's attached :class:`~repro.engine.LayoutEngine`,
    so repeated calls share cached compiled plans across callsites.
    """
    from repro.engine import engine_for

    return engine_for(tree).route(records, backend=backend)
