"""Pallas TPU kernels for batched record routing (paper Sec 3.1).

TPU adaptation (DESIGN.md §3): the paper's CPU router chases pointers down
the tree.  On TPU we factorize routing into two dense, gather-free kernels:

  1. ``eval_cuts_kernel`` — evaluate *every* candidate cut for a record tile:
       * column selection as a one-hot matmul (MXU),
       * IN-set membership as a global categorical one-hot (iota compares,
         VPU) times the packed membership masks (MXU),
       * advanced (col-vs-col) predicates as static column slices (VPU).
  2. ``locate_leaf_kernel`` — the *path-constraint* reformulation of tree
     descent: leaf ``l`` owns record ``r`` iff r's predicate vector M[r]
     satisfies every (cut, direction) constraint on l's root path, i.e.

         viol[r, l] = (1 - M[r]) @ PathPos[:, l] + M[r] @ PathNeg[:, l] == 0

     Two MXU matmuls replace ``depth`` sequential gathers; the unique
     zero-violation leaf is recovered with a weighted mask reduction.

All integer data is dictionary-encoded and must satisfy dom < 2**24 so
float32 MXU arithmetic is exact.  Dots whose operands carry values above
256 (dictionary codes, leaf ids) ask for ``Precision.HIGHEST``: Mosaic's
default f32 matmul may round operands to bf16.

Mosaic lowers f32 selects and compares, integer iota, and neither scatters
nor selects over bool vectors, so every mask in the kernel bodies is an
f32 0/1 array and every iota is int32 cast to f32.
"""

from __future__ import annotations

# qdlint: deterministic-module

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HIGHEST = jax.lax.Precision.HIGHEST


def f32_iota(shape: tuple[int, int], axis: int) -> jnp.ndarray:
    """Integer iota cast to f32 (Mosaic has no float iota)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis).astype(
        jnp.float32
    )


def eval_cut_tile(
    records,  # (TM, D) f32 — record tile (dictionary codes)
    dim_onehot,  # (D, C) f32 — one-hot of each cut's column
    cutpoint,  # (1, C) f32
    in_mask_t,  # (B, C) f32 — transposed IN membership masks
    is_cat,  # (1, D) f32 — 1.0 where dim is categorical
    cat_off,  # (1, D) f32 — bit-space offset per dim (0 for numeric)
    adv_cols,  # (A3, 3) f32 — rows: (col_a, op, col_b); A3 = max(n_adv,1)
    adv_sel,  # (A3, C) f32 — one-hot map adv id -> cut column
    kind,  # (1, C) f32 — cut kind per column: 0 range, 1 in, 2 adv
    *,
    n_adv: int,
    n_cat_bits: int,
):
    """Predicate matrix of one record tile, shared by both routing kernels.

    Returns ``(m, go, adv_res)``: the (TM, C) 0/1 predicate matrix, the
    (TM, B) global categorical one-hot and the (TM, A3) advanced-predicate
    truth bits.
    """
    tm, d_total = records.shape

    # -- range cuts: select each cut's column, compare against cutpoint ----
    vals = jnp.dot(
        records, dim_onehot, preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )  # (TM, C)
    rng = (vals < cutpoint).astype(jnp.float32)

    # -- IN cuts: global categorical one-hot  ×  membership masks ----------
    # GO[r, b] = 1 iff some categorical dim d has records[r, d] + off_d == b.
    # in_mask rows are zero outside their own dim segment, so the cross-dim
    # bits never contribute to the product.
    bit_iota = f32_iota((tm, n_cat_bits), 1)
    bitpos = records + cat_off  # (TM, D); junk for numeric dims
    go = jnp.zeros((tm, n_cat_bits), jnp.float32)
    for d in range(d_total):  # static loop over table columns
        hit = (bit_iota == bitpos[:, d : d + 1]).astype(jnp.float32)
        go = go + hit * is_cat[:, d : d + 1]
    inm = jnp.dot(go, in_mask_t, preferred_element_type=jnp.float32)
    inm = (inm > 0.5).astype(jnp.float32)

    # -- advanced cuts: static small loop over binary predicates -----------
    a3 = adv_sel.shape[0]
    adv_res = jnp.zeros((tm, a3), jnp.float32)
    advm = jnp.zeros_like(vals)
    if n_adv > 0:
        d_iota = f32_iota((1, d_total), 1)
        a_iota = f32_iota((1, a3), 1)
        for j in range(n_adv):  # n_adv is small and static (paper Sec 6.1)
            row = adv_cols[j : j + 1, :]  # (1, 3)
            col_a, op, col_b = row[:, 0:1], row[:, 1:2], row[:, 2:3]
            # one-hot select the two columns (dynamic col id, static loop
            # j); the (1, D) masks broadcast over sublanes only
            va = jnp.sum(
                records * (d_iota == col_a).astype(jnp.float32),
                axis=1, keepdims=True,
            )
            vb = jnp.sum(
                records * (d_iota == col_b).astype(jnp.float32),
                axis=1, keepdims=True,
            )
            lt = (va < vb).astype(jnp.float32)  # (TM, 1)
            eq = (va == vb).astype(jnp.float32)
            gt = 1.0 - lt - eq

            def is_op(k):  # (1, 1) 0/1 weight of comparison op k
                return (op == float(k)).astype(jnp.float32)

            # ops 0..4 are < <= > >= ==; anything else is != (predicates.py)
            t = (
                is_op(0) * lt
                + is_op(1) * (lt + eq)
                + is_op(2) * gt
                + is_op(3) * (gt + eq)
                + is_op(4) * eq
                + (op > 4.5).astype(jnp.float32) * (1.0 - eq)
            )
            adv_res = adv_res + t * (a_iota == float(j)).astype(jnp.float32)
        advm = jnp.dot(adv_res, adv_sel, preferred_element_type=jnp.float32)

    m = jnp.where(kind == 0.0, rng, jnp.where(kind == 1.0, inm, advm))
    return m, go, adv_res


# ---------------------------------------------------------------------------
# Kernel 1: predicate-matrix evaluation
# ---------------------------------------------------------------------------
def _eval_cuts_kernel(  # qdlint: jit-body
    # inputs (VMEM refs; shapes as in eval_cut_tile)
    records_ref,
    dim_onehot_ref,
    cutpoint_ref,
    in_mask_ref,
    cat_onehot_dims_ref,
    cat_offset_ref,
    adv_cols_ref,
    adv_sel_ref,
    kind_ref,
    # outputs
    m_ref,  # (TM, C) f32 — predicate matrix tile (0.0 / 1.0)
    *,
    n_adv: int,
    n_cat_bits: int,
):
    m, _, _ = eval_cut_tile(
        records_ref[...],
        dim_onehot_ref[...],
        cutpoint_ref[...],
        in_mask_ref[...],
        cat_onehot_dims_ref[...],
        cat_offset_ref[...],
        adv_cols_ref[...],
        adv_sel_ref[...],
        kind_ref[...],
        n_adv=n_adv,
        n_cat_bits=n_cat_bits,
    )
    m_ref[...] = m


@functools.partial(
    jax.jit, static_argnames=("tile_m", "n_cat_bits", "n_adv", "interpret")
)
def eval_cuts_pallas(
    records_f32: jnp.ndarray,  # (M, D) f32, M % tile_m == 0
    dim_onehot: jnp.ndarray,  # (D, C)
    cutpoint: jnp.ndarray,  # (1, C)
    in_mask_t: jnp.ndarray,  # (B, C)
    is_cat_row: jnp.ndarray,  # (1, D)
    cat_offset_row: jnp.ndarray,  # (1, D)
    adv_cols: jnp.ndarray,  # (A3, 3)
    adv_sel: jnp.ndarray,  # (A3, C)
    kind_row: jnp.ndarray,  # (1, C)
    *,
    tile_m: int,
    n_cat_bits: int,
    n_adv: int,
    interpret: bool,
) -> jnp.ndarray:
    m, d = records_f32.shape
    c = dim_onehot.shape[1]
    grid = (m // tile_m,)
    kernel = functools.partial(
        _eval_cuts_kernel, n_adv=n_adv, n_cat_bits=n_cat_bits
    )
    full = lambda *shape: [pl.BlockSpec(shape, lambda i: (0,) * len(shape))]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, d), lambda i: (i, 0)),  # records
            *full(d, c),  # dim_onehot
            *full(1, c),  # cutpoint
            *full(in_mask_t.shape[0], c),  # in_mask^T
            *full(1, d),  # is_cat
            *full(1, d),  # cat_offset
            *full(adv_cols.shape[0], 3),  # adv_cols
            *full(adv_sel.shape[0], c),  # adv_sel
            *full(1, c),  # kind
        ],
        out_specs=pl.BlockSpec((tile_m, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, c), jnp.float32),
        interpret=interpret,
    )(
        records_f32,
        dim_onehot,
        cutpoint,
        in_mask_t,
        is_cat_row,
        cat_offset_row,
        adv_cols,
        adv_sel,
        kind_row,
    )


# ---------------------------------------------------------------------------
# Kernel 2: path-constraint leaf location
# ---------------------------------------------------------------------------
def _locate_leaf_kernel(  # qdlint: jit-body
    m_ref,  # (TM, C) f32 — predicate-matrix tile
    pathpos_ref,  # (C, TL) f32 — 1 iff leaf's path requires cut true
    pathneg_ref,  # (C, TL) f32 — 1 iff leaf's path requires cut false
    leafid_ref,  # (1, TL) f32 — global leaf index + 1 (0 ⇒ padding)
    out_ref,  # (TM, 1) f32 — accumulates (bid + 1) of the unique hit
):
    l_step = pl.program_id(1)

    @pl.when(l_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = m_ref[...]
    viol = jnp.dot(
        1.0 - m, pathpos_ref[...], preferred_element_type=jnp.float32
    ) + jnp.dot(m, pathneg_ref[...], preferred_element_type=jnp.float32)
    hit = (viol < 0.5).astype(jnp.float32)  # (TM, TL)
    # each record matches exactly one (unpadded) leaf across all L tiles
    partial = jnp.dot(
        hit, leafid_ref[...].T, preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )  # (TM, 1)
    out_ref[...] += partial


@functools.partial(
    jax.jit, static_argnames=("tile_m", "tile_l", "interpret")
)
def locate_leaf_pallas(
    m_mat: jnp.ndarray,  # (M, C) f32
    pathpos: jnp.ndarray,  # (C, L) f32
    pathneg: jnp.ndarray,  # (C, L) f32
    leafid: jnp.ndarray,  # (1, L) f32 — bid + 1, zero on padded columns
    *,
    tile_m: int,
    tile_l: int,
    interpret: bool,
) -> jnp.ndarray:
    m, c = m_mat.shape
    n_leaf = pathpos.shape[1]
    grid = (m // tile_m, n_leaf // tile_l)
    out = pl.pallas_call(
        _locate_leaf_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, c), lambda i, j: (i, 0)),
            pl.BlockSpec((c, tile_l), lambda i, j: (0, j)),
            pl.BlockSpec((c, tile_l), lambda i, j: (0, j)),
            pl.BlockSpec((1, tile_l), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_m, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        interpret=interpret,
    )(m_mat, pathpos, pathneg, leafid)
    return out[:, 0] - 1.0  # back to 0-based BIDs; padding rows ⇒ -1
