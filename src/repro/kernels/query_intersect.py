"""Pallas TPU kernel for query↔block intersection + fused skip counting.

Computes, for a tile of block descriptions × a tile of workload conjuncts
(paper Sec 3.3):

    hits[l, c]  = 1  iff block l may contain records matching conjunct c
    scanned[c] += Σ_l |block l| · hits[l, c]      (fused Eq.-1 reduction)

Numeric box overlap is a static loop of broadcast compares (VPU); the
categorical any-shared-value test per dim is a mask matmul over that dim's
bit segment (MXU); advanced-cut polarity checks are a small static loop.

Grid = (n_conj_tiles, n_leaf_tiles) with the *leaf* axis innermost so the
``scanned`` accumulator block (0, c) is revisited in consecutive grid steps.

Block operands are block-major (one row per block) and conjunct operands
conjunct-major (one column per conjunct), so every test slices 2-D columns
and rows and sums failures into one (TL, TC) count.  Slicing 1-D vectors
and reshaping them instead kept one relayout per dim alive and overflowed
the 16 MB of scoped VMEM on a TPU v5e at 128 × 128 tiles.
"""

from __future__ import annotations

# qdlint: deterministic-module

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _intersect_kernel(  # qdlint: jit-body
    leaf_lo_ref,  # (TL, D) f32
    leaf_hi_ref,  # (TL, D) f32
    leaf_cat_ref,  # (TL, B) f32
    leaf_advt_ref,  # (TL, A) f32 — may contain satisfying records
    leaf_advf_ref,  # (TL, A) f32 — may contain violating records
    leaf_size_ref,  # (1, TL) f32
    q_lo_ref,  # (D, TC) f32 — conjunct-major: one column per conjunct
    q_hi_ref,  # (D, TC) f32
    q_cat_ref,  # (B, TC) f32
    q_reqt_ref,  # (A, TC) f32 — conjunct requires pred true
    q_reqf_ref,  # (A, TC) f32 — conjunct requires pred false
    hits_ref,  # out (TL, TC) f32
    scanned_ref,  # out (1, TC) f32, accumulated over leaf tiles
    *,
    numeric_dims: tuple[int, ...],
    cat_segments: tuple[tuple[int, int], ...],
    n_adv: int,
):
    i_leaf = pl.program_id(1)

    @pl.when(i_leaf == 0)
    def _init():
        scanned_ref[...] = jnp.zeros_like(scanned_ref)

    leaf_lo, leaf_hi = leaf_lo_ref[...], leaf_hi_ref[...]
    q_lo, q_hi = q_lo_ref[...], q_hi_ref[...]
    # every test below counts one failed requirement per (block, conjunct);
    # a (TL, 1) leaf column meets a (1, TC) conjunct row, so each operand
    # broadcasts along one axis only
    miss = jnp.zeros((leaf_lo.shape[0], q_lo.shape[1]), jnp.float32)

    # numeric box overlap: max(lo) < min(hi), per dim (static unroll)
    for d in numeric_dims:
        lo = jnp.maximum(leaf_lo[:, d : d + 1], q_lo[d : d + 1, :])
        hi = jnp.minimum(leaf_hi[:, d : d + 1], q_hi[d : d + 1, :])
        miss = miss + (lo >= hi).astype(jnp.float32)

    # categorical: each dim must share ≥1 allowed value (mask matmul per dim)
    leaf_cat, q_cat = leaf_cat_ref[...], q_cat_ref[...]
    for (s, e) in cat_segments:
        shared = jnp.dot(
            leaf_cat[:, s:e], q_cat[s:e, :],
            preferred_element_type=jnp.float32,
        )
        miss = miss + (shared < 0.5).astype(jnp.float32)

    # advanced-cut polarity compatibility
    may_t, may_f = leaf_advt_ref[...], leaf_advf_ref[...]
    req_t, req_f = q_reqt_ref[...], q_reqf_ref[...]
    for a in range(n_adv):
        miss = miss + req_t[a : a + 1, :] * (1.0 - may_t[:, a : a + 1])
        miss = miss + req_f[a : a + 1, :] * (1.0 - may_f[:, a : a + 1])

    ok = (miss < 0.5).astype(jnp.float32)
    hits_ref[...] = ok
    # block sizes reach the millions: keep the f32 matmul exact
    scanned_ref[...] += jnp.dot(
        leaf_size_ref[...], ok, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "tile_l", "tile_c", "numeric_dims", "cat_segments", "n_adv",
        "interpret",
    ),
)
def query_intersect_pallas(
    leaf_lo, leaf_hi, leaf_cat, leaf_advt, leaf_advf, leaf_size,
    q_lo, q_hi, q_cat, q_reqt, q_reqf,
    *,
    tile_l: int,
    tile_c: int,
    numeric_dims: tuple[int, ...],
    cat_segments: tuple[tuple[int, int], ...],
    n_adv: int,
    interpret: bool,
):
    l, d = leaf_lo.shape
    c = q_lo.shape[1]
    b = leaf_cat.shape[1]
    a = leaf_advt.shape[1]
    grid = (c // tile_c, l // tile_l)  # leaf axis innermost (accumulator)
    kernel = functools.partial(
        _intersect_kernel,
        numeric_dims=numeric_dims,
        cat_segments=cat_segments,
        n_adv=n_adv,
    )
    leaf_spec = lambda width: pl.BlockSpec(
        (tile_l, width), lambda j, i: (i, 0)
    )
    conj_spec = lambda width: pl.BlockSpec(
        (width, tile_c), lambda j, i: (0, j)
    )
    hits, scanned = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            leaf_spec(d),  # leaf_lo
            leaf_spec(d),  # leaf_hi
            leaf_spec(b),  # leaf_cat
            leaf_spec(a),  # leaf_advt
            leaf_spec(a),  # leaf_advf
            pl.BlockSpec((1, tile_l), lambda j, i: (0, i)),  # leaf_size
            conj_spec(d),  # q_lo
            conj_spec(d),  # q_hi
            conj_spec(b),  # q_cat
            conj_spec(a),  # q_reqt
            conj_spec(a),  # q_reqf
        ],
        out_specs=[
            pl.BlockSpec((tile_l, tile_c), lambda j, i: (i, j)),
            pl.BlockSpec((1, tile_c), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((l, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        interpret=interpret,
    )(
        leaf_lo, leaf_hi, leaf_cat, leaf_advt, leaf_advf, leaf_size,
        q_lo, q_hi, q_cat, q_reqt, q_reqf,
    )
    return hits, scanned
