"""Fused single-pass ingestion kernel: route + tighten in one tiled sweep.

The two-pass hot path reads every record twice — once to route it
(``eval_cuts`` → ``locate_leaf``, paper Sec 3.1) and once to min-max-
tighten its destination leaf's description (``IncrementalTightener``,
Sec 3.2).  Ingestion is I/O-bound, so on the roofline that second pass
halves the attainable throughput.  This kernel does both in ONE pass:

    grid = (m // tile_m, l_pad // tile_l)   — leaf axis innermost

* At each record tile's first leaf step (``j == 0``) the full predicate
  matrix M, the global categorical one-hot GO, and the advanced-cut truth
  bits are evaluated once (the ``eval_cuts`` math) and stashed in VMEM
  scratch — the TPU grid runs sequentially on one core, so scratch
  persists across the ``j`` steps that reuse them.
* At every (record tile i, leaf tile j) step the path-constraint matmuls
  recover the hit matrix (the ``locate_leaf`` math).  BIDs accumulate over
  ``j`` in the revisit pattern of ``query_intersect_pallas``; the per-leaf
  aggregates — counts, min/max bounds, categorical presence, advanced-cut
  truth bits — reduce into *full-array* accumulator outputs whose block
  index never changes, i.e. they stay resident in VMEM for the whole grid
  and are flushed to HBM exactly once.

Padding rows (``valid == 0``) still produce a bid — identical to
``locate_leaf_pallas``, the caller slices them off — but are masked out of
every aggregate, so the partials cover exactly the real records.

All values are dictionary codes < 2**24, so f32 mins/maxes/sums are exact
and the host-side int64 conversion (``engine/backends.py``) reproduces the
numpy tightener bit-for-bit.
"""

from __future__ import annotations

# qdlint: deterministic-module

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.route_records import HIGHEST, eval_cut_tile

# f32-exact sentinel beyond any dictionary code (codes < 2**24)
BIG = float(2**25)

# Largest padded leaf count whose full-array accumulators fit the 16 MB of
# scoped VMEM on a TPU v5e at the TPC-H widths (D=21, 256 categorical
# bits): 8192 leaves need 8 MB for the categorical accumulator alone.
# Tiling the accumulators over the leaf axis would lift the ceiling.
MAX_LEAF_BUCKET = 4096


def _fused_ingest_kernel(  # qdlint: jit-body
    # inputs (VMEM refs)
    records_ref,  # (TM, D) f32 — record tile (dictionary codes)
    valid_ref,  # (TM, 1) f32 — 1.0 real record, 0.0 padding row
    dim_onehot_ref,  # (D, C) f32
    cutpoint_ref,  # (1, C) f32
    in_mask_ref,  # (B, C) f32 — transposed IN membership masks
    is_cat_ref,  # (1, D) f32
    cat_off_ref,  # (1, D) f32
    adv_cols_ref,  # (A3, 3) f32 — rows: (col_a, op, col_b)
    adv_sel_ref,  # (A3, C) f32 — one-hot map adv id -> cut column
    kind_ref,  # (1, C) f32
    pathpos_ref,  # (C, TL) f32
    pathneg_ref,  # (C, TL) f32
    leafid_ref,  # (1, TL) f32 — global leaf index + 1 (0 ⇒ padding)
    # outputs
    bids_ref,  # (TM, 1) f32 — accumulates (bid + 1), revisited over j
    counts_ref,  # (1, L) f32 — full-array accumulator
    lo_ref,  # (L, D) f32 — full-array accumulator (init +BIG)
    hi_ref,  # (L, D) f32 — full-array accumulator (init -BIG)
    cat_ref,  # (L, B) f32 — full-array accumulator (presence bits)
    advt_ref,  # (L, A3) f32 — full-array accumulator (truth bits)
    advf_ref,  # (L, A3) f32 — full-array accumulator (falsity bits)
    # scratch (persists across grid steps: the grid is sequential)
    m_scratch,  # (TM, C) f32 — predicate matrix for record tile i
    go_scratch,  # (TM, B) f32 — global categorical one-hot
    adv_scratch,  # (TM, A3) f32 — advanced-predicate truth per record
    *,
    n_adv: int,
    n_cat_bits: int,
    tile_l: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_accumulators():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        lo_ref[...] = jnp.full_like(lo_ref, BIG)
        hi_ref[...] = jnp.full_like(hi_ref, -BIG)
        cat_ref[...] = jnp.zeros_like(cat_ref)
        advt_ref[...] = jnp.zeros_like(advt_ref)
        advf_ref[...] = jnp.zeros_like(advf_ref)

    @pl.when(j == 0)
    def _eval_cuts_once_per_record_tile():
        bids_ref[...] = jnp.zeros_like(bids_ref)
        m, go, adv_res = eval_cut_tile(
            records_ref[...],
            dim_onehot_ref[...],
            cutpoint_ref[...],
            in_mask_ref[...],
            is_cat_ref[...],
            cat_off_ref[...],
            adv_cols_ref[...],
            adv_sel_ref[...],
            kind_ref[...],
            n_adv=n_adv,
            n_cat_bits=n_cat_bits,
        )
        m_scratch[...] = m
        go_scratch[...] = go
        adv_scratch[...] = adv_res

    # -- leaf location for this (record tile, leaf tile) -------------------
    m = m_scratch[...]
    viol = jnp.dot(
        1.0 - m, pathpos_ref[...], preferred_element_type=jnp.float32
    ) + jnp.dot(m, pathneg_ref[...], preferred_element_type=jnp.float32)
    hit = (viol < 0.5).astype(jnp.float32)  # (TM, TL)
    # bids: identical to locate_leaf_pallas (padding rows included; the
    # host slices them off) — accumulated across leaf tiles
    bids_ref[...] += jnp.dot(
        hit, leafid_ref[...].T, preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )

    # -- per-leaf tightening partials (valid rows only) ---------------------
    valid = valid_ref[...]  # (TM, 1)
    hitv = hit * valid  # (TM, TL)
    sl = pl.ds(j * tile_l, tile_l)

    tile_counts = jnp.sum(hitv, axis=0, keepdims=True)  # (1, TL)
    counts_ref[:, sl] = counts_ref[:, sl] + tile_counts

    records = records_ref[...]
    lo_cols = []
    hi_cols = []
    for d in range(records.shape[1]):  # static loop over table columns
        col = records[:, d][:, None]  # (TM, 1)
        lo_cols.append(jnp.min(jnp.where(hitv > 0.5, col, BIG), axis=0))
        hi_cols.append(jnp.max(jnp.where(hitv > 0.5, col, -BIG), axis=0))
    lo_ref[sl, :] = jnp.minimum(
        lo_ref[sl, :], jnp.stack(lo_cols, axis=1)
    )
    hi_ref[sl, :] = jnp.maximum(
        hi_ref[sl, :], jnp.stack(hi_cols, axis=1)
    )

    # categorical presence: any hit record carrying bit b (mask matmul, MXU)
    catp = jnp.dot(
        hitv.T, go_scratch[...], preferred_element_type=jnp.float32
    )  # (TL, B)
    cat_ref[sl, :] = jnp.maximum(
        cat_ref[sl, :], (catp > 0.5).astype(jnp.float32)
    )

    # advanced-cut truth bits: Σ hitv·t  and  (Σ hitv) − Σ hitv·t
    advtp = jnp.dot(
        hitv.T, adv_scratch[...], preferred_element_type=jnp.float32
    )  # (TL, A3)
    advfp = tile_counts[0][:, None] - advtp
    advt_ref[sl, :] = jnp.maximum(
        advt_ref[sl, :], (advtp > 0.5).astype(jnp.float32)
    )
    advf_ref[sl, :] = jnp.maximum(
        advf_ref[sl, :], (advfp > 0.5).astype(jnp.float32)
    )


@functools.partial(
    jax.jit,
    static_argnames=("tile_m", "tile_l", "n_cat_bits", "n_adv", "interpret"),
)
def fused_ingest_pallas(
    records_f32: jnp.ndarray,  # (M, D) f32, M % tile_m == 0
    valid: jnp.ndarray,  # (M, 1) f32
    dim_onehot: jnp.ndarray,  # (D, C)
    cutpoint: jnp.ndarray,  # (1, C)
    in_mask_t: jnp.ndarray,  # (B, C)
    is_cat_row: jnp.ndarray,  # (1, D)
    cat_offset_row: jnp.ndarray,  # (1, D)
    adv_cols: jnp.ndarray,  # (A3, 3)
    adv_sel: jnp.ndarray,  # (A3, C)
    kind_row: jnp.ndarray,  # (1, C)
    pathpos: jnp.ndarray,  # (C, L)
    pathneg: jnp.ndarray,  # (C, L)
    leafid: jnp.ndarray,  # (1, L)
    *,
    tile_m: int,
    tile_l: int,
    n_cat_bits: int,
    n_adv: int,
    interpret: bool,
):
    """One fused pass: returns (bids+1, counts, lo, hi, cat, advt, advf).

    ``bids`` is (M, 1) f32 holding bid + 1 (0 on rows matching no real
    leaf, i.e. never for valid rows); all aggregates are f32 at the padded
    leaf geometry ``L`` and get sliced/converted by the caller.
    """
    m, d = records_f32.shape
    c = dim_onehot.shape[1]
    b = in_mask_t.shape[0]
    a3 = adv_sel.shape[0]
    n_leaf = pathpos.shape[1]
    grid = (m // tile_m, n_leaf // tile_l)  # leaf axis innermost
    kernel = functools.partial(
        _fused_ingest_kernel,
        n_adv=n_adv,
        n_cat_bits=n_cat_bits,
        tile_l=tile_l,
    )
    full = lambda *shape: [
        pl.BlockSpec(shape, lambda i, j: (0,) * len(shape))
    ]
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, d), lambda i, j: (i, 0)),  # records
            pl.BlockSpec((tile_m, 1), lambda i, j: (i, 0)),  # valid
            *full(d, c),  # dim_onehot
            *full(1, c),  # cutpoint
            *full(b, c),  # in_mask^T
            *full(1, d),  # is_cat
            *full(1, d),  # cat_offset
            *full(a3, 3),  # adv_cols
            *full(a3, c),  # adv_sel
            *full(1, c),  # kind
            pl.BlockSpec((c, tile_l), lambda i, j: (0, j)),  # pathpos
            pl.BlockSpec((c, tile_l), lambda i, j: (0, j)),  # pathneg
            pl.BlockSpec((1, tile_l), lambda i, j: (0, j)),  # leafid
        ],
        out_specs=[
            pl.BlockSpec((tile_m, 1), lambda i, j: (i, 0)),  # bids
            *full(1, n_leaf),  # counts
            *full(n_leaf, d),  # lo
            *full(n_leaf, d),  # hi
            *full(n_leaf, b),  # cat
            *full(n_leaf, a3),  # advt
            *full(n_leaf, a3),  # advf
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, n_leaf), jnp.float32),
            jax.ShapeDtypeStruct((n_leaf, d), jnp.float32),
            jax.ShapeDtypeStruct((n_leaf, d), jnp.float32),
            jax.ShapeDtypeStruct((n_leaf, b), jnp.float32),
            jax.ShapeDtypeStruct((n_leaf, a3), jnp.float32),
            jax.ShapeDtypeStruct((n_leaf, a3), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_m, c), jnp.float32),
            pltpu.VMEM((tile_m, b), jnp.float32),
            pltpu.VMEM((tile_m, a3), jnp.float32),
        ],
        interpret=interpret,
    )(
        records_f32, valid,
        dim_onehot, cutpoint, in_mask_t, is_cat_row, cat_offset_row,
        adv_cols, adv_sel, kind_row,
        pathpos, pathneg, leafid,
    )
    return outs
