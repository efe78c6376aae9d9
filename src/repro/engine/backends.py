"""Routing/intersection backends for the LayoutEngine.

Each backend registers itself under a name (replacing the stringly-typed
``routing.route(..., backend=...)`` dispatch) and implements the same two
operations against a ``FrozenQdTree``:

  * ``route(tree, cache, records)``      — record batch → BIDs (int32)
  * ``query_hits(tree, cache, wt)``      — (n_leaves, n_queries) bool

All backends are bit-identical to the numpy oracles in ``repro.core``; the
jitted jnp and Pallas paths additionally pull their packed operands from the
engine's :class:`~repro.engine.plan.PlanCache`, so same-bucket batches reuse
compilations (zero retracing — asserted via ``plan.trace_counts``).
"""

from __future__ import annotations

# qdlint: deterministic-module

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import query as qry
from repro.core.qdtree import FrozenQdTree
from repro.engine import plan as planlib
from repro.engine.plan import (
    LANE,
    CompiledPlan,
    PlanCache,
    PlanKey,
    count_trace,
    interpret_default,
    pad_bucket,
)

_REGISTRY: dict[str, "Backend"] = {}


def register_backend(name: str):
    """Class decorator: instantiate and register a backend under ``name``."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls()
        return cls

    return deco


def get_backend(name: str) -> "Backend":
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


class Backend:
    """Interface: route records and intersect queries for one frozen tree."""

    name: str = "?"

    def route(
        self,
        tree: FrozenQdTree,
        cache: PlanCache,
        records: np.ndarray,
        **opts,
    ) -> np.ndarray:
        raise NotImplementedError

    def query_hits(
        self,
        tree: FrozenQdTree,
        cache: PlanCache,
        wt: qry.WorkloadTensors,
        **opts,
    ) -> np.ndarray:
        raise NotImplementedError

    def fused_outputs(
        self,
        tree: FrozenQdTree,
        cache: PlanCache,
        records: np.ndarray,
        return_bids: bool = True,
        **opts,
    ):
        """Phase one of a single-pass route + tighten step: the batch
        staged, run, and its outputs pulled to the host.

        Returns ``(bids int32 (m,), outputs)``; :meth:`fold_outputs` turns
        ``outputs`` into the batch's ``TightenPartial``.  The base
        implementation is the legacy two-pass fallback, whose outputs
        already are the partial, so every backend has a fused entry point
        even before it grows a fused kernel.

        ``return_bids=False`` lets a caller that only folds partials
        (shard workers streaming aggregates, tighten-only ingest) skip
        the per-row block-id device→host transfer — the largest host
        sync of the warm loop; the first tuple element is then ``None``.
        The compiled plan is identical either way (no retrace).
        """
        from repro.core.qdtree import IncrementalTightener

        bids = self.route(tree, cache, records, **opts)
        t = IncrementalTightener(tree)
        t.update(records, bids)
        return (bids if return_bids else None), t.as_partial()

    def fold_outputs(self, tree: FrozenQdTree, outputs):
        """Phase two: the outputs of :meth:`fused_outputs` as a
        ``TightenPartial`` (the base's outputs already are one)."""
        return outputs


# ---------------------------------------------------------------------------
# numpy oracle
# ---------------------------------------------------------------------------
@register_backend("numpy")
class NumpyBackend(Backend):
    def route(self, tree, cache, records, **opts):
        return tree.route(records)

    def query_hits(self, tree, cache, wt, **opts):
        conj = qry.conjuncts_intersect(
            tree.leaf_lo, tree.leaf_hi, tree.leaf_cat, tree.leaf_adv, wt,
            tree.schema,
        )
        return qry.queries_intersect(conj, wt)

    def fused_outputs(self, tree, cache, records, return_bids=True, **opts):
        # the numpy oracle IS the bit-identity reference for every fused
        # backend (kernels/ref.py); its outputs already are the partial
        from repro.kernels.ref import fused_ingest_ref

        bids, partial = fused_ingest_ref(tree, records)
        return (bids if return_bids else None), partial


# ---------------------------------------------------------------------------
# jitted jnp route and fused ingest
# ---------------------------------------------------------------------------
# The fused jax ingest step locates rows by the path-matrix product while
# its plan's leaf_bucket × cut_bucket is at most this, else by the descent.
# Whole step on one TPU v5e, 65,536 TPC-H rows, greedy trees of the
# tpch_sf1 benchmark workload (PERF.md §6): product against descent
# 25.9 / 93.0 ms at 1024 × 100, 50.0 / 116.8 ms at 4096 × 196 and
# 53.3 / 118.2 ms at 16,384 × 196.  The product won at every geometry
# measured, so the descent is kept only above the largest.
PATHS_MAX_CELLS = 16_384 * 196


def _locate_descent(M, ta, depth):
    """Block id of each row by the level-synchronous descent: ``depth``
    rounds of per-row gathers through the flat tree arrays ``ta``."""
    node = jnp.zeros(M.shape[0], jnp.int32)

    def body(_, node):
        cid = ta["cut_id"][node]
        pred = jnp.take_along_axis(
            M, jnp.clip(cid, 0)[:, None].astype(jnp.int32), axis=1
        )[:, 0]
        nxt = jnp.where(pred, ta["left"][node], ta["right"][node])
        return jnp.where(cid >= 0, nxt, node)

    node = jax.lax.fori_loop(0, depth, body, node)
    return ta["leaf_bid"][node]


def _locate_paths(M, pa):
    """Block id of each row from the leaves' path constraints.

    A row lies in leaf ``l`` exactly when it violates none of the cuts on
    ``l``'s path: ``viol = (1 - M) @ pathpos + M @ pathneg``, which is
    ``path_len + M @ path_diff`` with ``path_diff = pathneg - pathpos``
    and ``path_len`` the column sums of ``pathpos``.  Every operand is
    0 or ±1 and a path has at most ``depth`` terms, so the product is
    exact at any matmul precision; the one column with no violation is
    the block id (padded columns always carry one).  On the TPU, XLA fuses
    the product with the compare and the argmax, so the (rows, leaves)
    counts are never written out.
    """
    viol = pa["path_len"] + jnp.dot(M.astype(jnp.float32), pa["path_diff"])
    return jnp.argmax(viol < 0.5, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("depth",))
def _route_jax_padded(records, ta, ca, depth):
    count_trace("route:jax")
    from repro.core.routing import eval_cuts_jax

    return _locate_descent(eval_cuts_jax(records, ca), ta, depth)


@functools.partial(
    jax.jit,
    static_argnames=("locate", "depth", "l_dump", "cat_cols", "cat_gemm",
                     "bits", "n_adv"),
)
def _ingest_jax_padded(records, valid, loc, ca, locate, depth, l_dump,
                       cat_cols, cat_gemm, bits, n_adv):
    """Fused single-pass ingest: predicate matrix, leaf location and the
    per-leaf reductions in one compiled program, so each record is
    touched once.

    * ``ca`` holds only the cuts the tree's internal nodes reference
      (pruned + remapped by ``_ingest_plan``), *grouped by kind*
      (``[range | IN | adv]`` segments, each padded to its own bucket),
      so range cuts are pure vector compares;
    * the IN segment is one product of the row's categorical one-hot
      (``bitvec``, its columns in ``cat_cols`` order) with the IN masks
      permuted into that order, ``bitvec @ mask_iT``: a mask is nonzero
      only in its column's bit segment and a row has one bit set per
      segment, so every cell is exactly 0 or 1 and equals the bit lookup
      at any matmul precision.  A code outside its column's domain, which
      the schema forbids, reads False here;
    * each row's block comes from one path-matrix product against the
      predicate matrix (``locate="paths"``, :func:`_locate_paths`), or
      from ``depth`` rounds of per-row gathers (``"descent"``) on a plan
      too large for the product (``PATHS_MAX_CELLS``); ``loc`` holds
      that mechanism's operands;
    * counts / categorical bits / adv flags all come out of ONE one-hot
      matmul (``leaf-onehotᵀ @ [1 | value-onehots | t | ~t]``) instead of
      per-element scatters.  The f32 accumulations are exact: 0/1
      summands, totals < 2**24.

    Padding rows are redirected to a dump row (``l_dump - 1``) that the
    caller slices off; dictionary codes are int32 throughout, so the
    aggregates convert to the tightener's int64 partials exactly.
    ``cat_cols`` is ``((dim, bit_offset, cardinality), ...)``;
    ``cat_gemm`` is False when the schema's bit layout is not contiguous
    in dim order: the stats then fall back to per-dim scatters.
    """
    count_trace("ingest:jax")
    m = records.shape[0]
    scope = jax.named_scope
    bitvec = None
    if cat_cols:
        # per-record categorical one-hot in ``cat_cols`` order, feeding
        # the IN product and, at the schema's bit layout, the stats matmul
        with scope(spans.STEP_CAT_STATS):
            bitvec = jnp.concatenate(
                [
                    (
                        records[:, dd, None]
                        == jnp.arange(card, dtype=records.dtype)[None, :]
                    ).astype(jnp.float32)
                    for dd, _off, card in cat_cols
                ],
                axis=1,
            )
    with scope(spans.STEP_PREDICATES):
        if n_adv:
            adv = ca["adv"]
            va = records[:, adv[:, 0]]
            vb = records[:, adv[:, 2]]
            op = adv[:, 1][None, :]
            t = jnp.select(
                [op == 0, op == 1, op == 2, op == 3, op == 4, op == 5],
                [va < vb, va <= vb, va > vb, va >= vb, va == vb, va != vb],
            ).astype(bool)  # select's int default would break ~t

        # kind-grouped predicate matrix: [range | IN | adv] segment columns
        rng_m = records[:, ca["dim_r"]] < ca["cut_r"][None, :]
        if bitvec is not None:
            with scope(spans.STEP_IN_LOOKUP):
                inm = jnp.dot(
                    bitvec, ca["mask_iT"],
                    preferred_element_type=jnp.float32,
                ) > 0.5
        else:  # no categorical column, so no IN cut
            inm = jnp.zeros((m, ca["mask_iT"].shape[1]), bool)
        if n_adv:
            advm = t[:, ca["advsel"]]
        else:
            advm = jnp.zeros((m, ca["advsel"].shape[0]), bool)
        M = jnp.concatenate([rng_m, inm, advm], axis=1)

    with scope(spans.STEP_LOCATE):
        if locate == "paths":
            bids = _locate_paths(M, loc)
        else:
            bids = _locate_descent(M, loc, depth)

    d = records.shape[1]
    i32 = jnp.iinfo(jnp.int32)
    agg = jnp.where(valid, bids, l_dump - 1).astype(jnp.int32)
    with scope(spans.STEP_MINMAX):
        lo = (
            jnp.full((l_dump, d), i32.max, jnp.int32).at[agg].min(records)
        )
        hi = (
            jnp.full((l_dump, d), i32.min, jnp.int32).at[agg].max(records)
        )

    with scope(spans.STEP_CAT_STATS):
        cols = [jnp.ones((m, 1), jnp.float32)]
        if cat_gemm and bitvec is not None:
            cols.append(bitvec)
        if n_adv:
            cols.append(t.astype(jnp.float32))
            cols.append((~t).astype(jnp.float32))
        onehot = (
            agg[:, None] == jnp.arange(l_dump, dtype=jnp.int32)[None, :]
        ).astype(jnp.float32)
        stats = onehot.T @ jnp.concatenate(cols, axis=1)
        counts = stats[:, 0].astype(jnp.int32)
        pos = 1
        if cat_gemm and bitvec is not None:
            cat = stats[:, pos : pos + bits] > 0
            pos += bits
        else:
            cat = jnp.zeros((l_dump, bits), bool)
            for dd, off, _card in cat_cols:
                cat = cat.at[agg, off + records[:, dd]].max(True)
        if n_adv:
            advt = stats[:, pos : pos + n_adv] > 0
            advf = stats[:, pos + n_adv : pos + 2 * n_adv] > 0
        else:
            advt = advf = jnp.zeros((l_dump, 1), bool)
    return bids, counts, lo, hi, cat, advt, advf


@functools.partial(
    jax.jit, static_argnames=("numeric_dims", "cat_segments", "n_adv")
)
def _conj_intersect_jax(leaf, q, numeric_dims, cat_segments, n_adv):
    count_trace("query:jax")
    lo = jnp.maximum(leaf["leaf_lo"][:, None, :], q["q_lo"][None, :, :])
    hi = jnp.minimum(leaf["leaf_hi"][:, None, :], q["q_hi"][None, :, :])
    boxes = lo < hi  # (L, C, D)
    shape = boxes.shape[:2]
    if numeric_dims:
        box_ok = boxes[:, :, jnp.asarray(numeric_dims)].all(axis=2)
    else:
        box_ok = jnp.ones(shape, bool)
    cat_ok = jnp.ones(shape, bool)
    for s, e in cat_segments:
        cat_ok &= (
            leaf["leaf_cat"][:, None, s:e] & q["q_cat"][None, :, s:e]
        ).any(axis=2)
    adv_ok = jnp.ones(shape, bool)
    if n_adv:
        req = q["q_adv"][:, :n_adv]  # (C, A)
        may_t = leaf["leaf_adv"][:, :, 0]  # (L, A)
        may_f = leaf["leaf_adv"][:, :, 1]
        ok = ~((req == qry.ADV_TRUE)[None, :, :] & ~may_t[:, None, :])
        ok &= ~((req == qry.ADV_FALSE)[None, :, :] & ~may_f[:, None, :])
        adv_ok = ok.all(axis=2)
    return box_ok & cat_ok & adv_ok


def _padded_workload_tensors(wt: qry.WorkloadTensors) -> dict:
    """Conjunct tensors padded to their bucket, device-resident.

    Cached on the (immutable) WorkloadTensors object itself, so scoring
    loops reuse the upload instead of re-padding and re-transferring.
    """
    cached = getattr(wt, "_jax_padded", None)
    if cached is not None:
        return cached
    nc = wt.n_conjuncts
    c_bucket = pad_bucket(nc, 8)

    def _padq(x, fill):
        out = np.full((c_bucket,) + x.shape[1:], fill, x.dtype)
        out[:nc] = x
        return out

    q = {
        "q_lo": jnp.asarray(_padq(wt.q_lo, 0)),
        "q_hi": jnp.asarray(_padq(wt.q_hi, 0)),  # empty box ⇒ no hit
        "q_cat": jnp.asarray(_padq(wt.q_cat, False)),
        "q_adv": jnp.asarray(_padq(wt.q_adv, 0)),
    }
    object.__setattr__(wt, "_jax_padded", q)
    return q


@register_backend("jax")
class JaxBackend(Backend):
    min_batch_bucket = 64

    def _route_plan(self, tree, cache):
        sig = planlib.tree_signature(tree)
        node_bucket = pad_bucket(tree.n_nodes, 16)
        cut_bucket = pad_bucket(tree.cuts.n_cuts, 16)
        depth_bucket = pad_bucket(tree.depth, 1)
        key = PlanKey(
            sig, "jax", 0, node_bucket, 0, cut_bucket, ("route", depth_bucket)
        )

        def build():
            ta = {
                k: jnp.asarray(v)
                for k, v in planlib.pack_tree_arrays(tree, node_bucket).items()
            }
            ca = {
                k: jnp.asarray(v)
                for k, v in planlib.pack_cut_arrays(tree, cut_bucket).items()
            }
            fn = functools.partial(
                _route_jax_padded, ta=ta, ca=ca, depth=depth_bucket
            )
            return CompiledPlan(key=key, fn=fn, operands={"ta": ta, "ca": ca},
                                meta={"depth": depth_bucket})

        return cache.get(key, build)

    def route(self, tree, cache, records, **opts):
        plan = self._route_plan(tree, cache)
        m = records.shape[0]
        m_bucket = pad_bucket(m, self.min_batch_bucket)
        padded = np.zeros((m_bucket, records.shape[1]), np.int32)
        padded[:m] = records
        out = plan.fn(jnp.asarray(padded))
        return np.asarray(out[:m]).astype(np.int32)

    def _ingest_plan(self, tree, cache):
        sig = planlib.tree_signature(tree)
        node_bucket = pad_bucket(tree.n_nodes, 16)
        leaf_bucket = pad_bucket(tree.n_leaves, 8)
        depth_bucket = pad_bucket(tree.depth, 1)
        # the ingest plan evaluates only the cuts the tree references —
        # the candidate table is typically several times larger — and
        # groups them by kind so range cuts stay pure compares and the
        # IN masks form one operand of the one-hot product
        from repro.core import predicates as preds

        used = np.unique(tree.cut_id[tree.cut_id >= 0]).astype(np.int64)
        kind_u = tree.cuts.kind[used]
        seg_r = used[kind_u == preds.KIND_RANGE]
        seg_i = used[kind_u == preds.KIND_IN]
        seg_a = used[kind_u == preds.KIND_ADV]
        nr_pad = pad_bucket(int(seg_r.size), 4)
        ni_pad = pad_bucket(int(seg_i.size), 4)
        na_pad = pad_bucket(int(seg_a.size), 4)
        cut_bucket = nr_pad + ni_pad + na_pad
        # dump row past the bucketed leaf axis absorbs padding rows
        l_dump = leaf_bucket + 1
        locate = (
            "paths" if leaf_bucket * cut_bucket <= PATHS_MAX_CELLS
            else "descent"
        )
        key = PlanKey(
            sig, "jax", 0, node_bucket, leaf_bucket, cut_bucket,
            ("ingest", locate, depth_bucket, nr_pad, ni_pad, na_pad),
        )

        def build():
            schema = tree.schema
            # cut ids into the grouped table: segment base + position
            # within segment
            remap = np.full(max(tree.cuts.n_cuts, 1), -1, np.int64)
            remap[seg_r] = np.arange(seg_r.size)
            remap[seg_i] = nr_pad + np.arange(seg_i.size)
            remap[seg_a] = nr_pad + ni_pad + np.arange(seg_a.size)
            if locate == "paths":
                pos, neg = planlib.path_matrices(tree)
                gpos = np.zeros((cut_bucket, leaf_bucket), np.float32)
                gneg = np.zeros((cut_bucket, leaf_bucket), np.float32)
                gpos[remap[used], : tree.n_leaves] = pos[used]
                gneg[remap[used], : tree.n_leaves] = neg[used]
                # a padded leaf column requires its first cut both true
                # and false, so it never matches
                gpos[0, tree.n_leaves:] = gneg[0, tree.n_leaves:] = 1.0
                loc_np = {
                    "path_diff": gneg - gpos,
                    "path_len": gpos.sum(axis=0),
                }
            else:
                loc_np = planlib.pack_tree_arrays(tree, node_bucket)
                cid = loc_np["cut_id"]
                loc_np["cut_id"] = np.where(
                    cid >= 0, remap[np.maximum(cid, 0)], -1
                ).astype(cid.dtype)
            ca_full = planlib.pack_cut_arrays(
                tree, pad_bucket(tree.cuts.n_cuts, 16)
            )

            def _segpad(x, seg, n_pad, fill):
                out = np.full((n_pad,) + x.shape[1:], fill, x.dtype)
                out[: seg.size] = x[seg]
                return out

            off = np.maximum(schema.cat_offsets, 0)
            bits = max(int(schema.total_cat_bits), 1)
            cat_cols = []
            running = 0
            cat_gemm = True
            for dd in np.nonzero(schema.is_categorical)[0]:
                card = int(schema.doms[dd])
                if int(off[dd]) != running:
                    cat_gemm = False  # unusual layout: scatter fallback
                cat_cols.append((int(dd), int(off[dd]), card))
                running += card
            cat_gemm = cat_gemm and (
                not cat_cols or running == int(schema.total_cat_bits)
            )
            # IN masks as the product's (bits, IN cuts) operand, their bit
            # columns permuted into the one-hot's ``cat_cols`` order
            bit_order = np.concatenate(
                [np.arange(o, o + c) for _d, o, c in cat_cols]
                or [np.zeros(0, np.int64)]
            )
            mask_i = _segpad(ca_full["in_mask"], seg_i, ni_pad, False)
            ca_np = {
                "dim_r": _segpad(ca_full["dim"], seg_r, nr_pad, 0),
                "cut_r": _segpad(ca_full["cutpoint"], seg_r, nr_pad, 0),
                "mask_iT": mask_i[:, bit_order].T.astype(np.float32),
                "advsel": _segpad(ca_full["adv_id"], seg_a, na_pad, 0),
                "adv": ca_full["adv"],
            }
            loc = {k: jnp.asarray(v) for k, v in loc_np.items()}
            ca = {k: jnp.asarray(v) for k, v in ca_np.items()}
            fn = functools.partial(
                _ingest_jax_padded, loc=loc, ca=ca, locate=locate,
                depth=depth_bucket, l_dump=l_dump, cat_cols=tuple(cat_cols),
                cat_gemm=cat_gemm, bits=bits, n_adv=tree.cuts.n_adv,
            )
            return CompiledPlan(
                key=key, fn=fn, operands={"loc": loc, "ca": ca},
                meta={"locate": locate, "depth": depth_bucket,
                      "l_dump": l_dump, "cat_gemm": cat_gemm,
                      "in_cuts": (int(seg_i.size), ni_pad),
                      "in_lookup": "product" if cat_cols else "none",
                      "cat_bits": bits},
            )

        return cache.get(key, build)

    def fused_outputs(self, tree, cache, records, return_bids=True, **opts):
        m = records.shape[0]
        L = tree.n_leaves
        with spans.span(spans.ENGINE_STAGE):
            plan = self._ingest_plan(tree, cache)
            m_bucket = pad_bucket(m, self.min_batch_bucket)
            padded = np.zeros((m_bucket, records.shape[1]), np.int32)
            padded[:m] = records
            valid = np.zeros(m_bucket, bool)
            valid[:m] = True
            bids, *aggs = plan.fn(jnp.asarray(padded), jnp.asarray(valid))
        with spans.span(spans.ENGINE_DEVICE_WAIT):
            aggs = tuple(np.asarray(a)[:L] for a in aggs)
            # partials-only callers skip the per-row D2H (the plan still
            # computes bids on device; only the host conversion is elided)
            bids = (
                np.asarray(bids[:m]).astype(np.int32) if return_bids else None
            )
        return bids, aggs

    def fold_outputs(self, tree, outputs):
        from repro.kernels.ref import partial_from_fused

        return partial_from_fused(tree, *outputs)

    def query_hits(self, tree, cache, wt, **opts):
        sig = planlib.tree_signature(tree)
        L = tree.n_leaves
        leaf_bucket = pad_bucket(L, 8)
        version = planlib.desc_version(tree)
        key = PlanKey(sig, "jax", 0, 0, leaf_bucket, 0, ("query", version))

        def build():
            schema = tree.schema
            leaf = {
                k: jnp.asarray(v)
                for k, v in planlib.pack_leaf_descs(tree, leaf_bucket).items()
            }
            off = schema.cat_offsets
            meta = {
                "numeric_dims": tuple(
                    int(i) for i in np.nonzero(~schema.is_categorical)[0]
                ),
                "cat_segments": tuple(
                    (int(off[d]), int(off[d]) + schema.columns[d].dom)
                    for d in np.nonzero(schema.is_categorical)[0]
                ),
            }
            # tighten superseded any older leaf-description plan — drop it
            # so long-lived ingest/score loops don't accumulate device copies
            cache.evict(
                lambda k: (
                    isinstance(k, PlanKey)
                    and k.sig == sig
                    and k.opts[:1] == ("query",)
                    and k.opts != ("query", version)
                )
            )
            return CompiledPlan(key=key, fn=None, operands=leaf, meta=meta)

        plan = cache.get(key, build)
        q = _padded_workload_tensors(wt)
        conj = _conj_intersect_jax(
            plan.operands, q,
            numeric_dims=plan.meta["numeric_dims"],
            cat_segments=plan.meta["cat_segments"],
            n_adv=tree.leaf_adv.shape[1],
        )
        conj_hits = np.asarray(conj)[:L, : wt.n_conjuncts]
        return qry.queries_intersect(conj_hits, wt)


# ---------------------------------------------------------------------------
# Pallas TPU kernels (compiled on a TPU, interpret mode elsewhere)
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("tile_m", "tile_l", "n_cat_bits", "n_adv", "interpret"),
)
def _route_pallas_padded(
    records_f32, k, *, tile_m, tile_l, n_cat_bits, n_adv, interpret
):
    count_trace("route:pallas")
    from repro.kernels import route_records as rk

    m_mat = rk.eval_cuts_pallas(
        records_f32,
        k["dim_onehot"],
        k["cutpoint"],
        k["in_mask_t"],
        k["is_cat"],
        k["cat_off"],
        k["adv_cols"],
        k["adv_sel"],
        k["kind"],
        tile_m=tile_m,
        n_cat_bits=n_cat_bits,
        n_adv=n_adv,
        interpret=interpret,
    )
    return rk.locate_leaf_pallas(
        m_mat,
        k["pathpos"],
        k["pathneg"],
        k["leafid"],
        tile_m=tile_m,
        tile_l=tile_l,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("tile_m", "tile_l", "n_cat_bits", "n_adv", "interpret"),
)
def _ingest_pallas_padded(
    records_f32, valid, k, *, tile_m, tile_l, n_cat_bits, n_adv, interpret
):
    count_trace("ingest:pallas")
    from repro.kernels import fused_ingest as fk

    return fk.fused_ingest_pallas(
        records_f32,
        valid,
        k["dim_onehot"],
        k["cutpoint"],
        k["in_mask_t"],
        k["is_cat"],
        k["cat_off"],
        k["adv_cols"],
        k["adv_sel"],
        k["kind"],
        k["pathpos"],
        k["pathneg"],
        k["leafid"],
        tile_m=tile_m,
        tile_l=tile_l,
        n_cat_bits=n_cat_bits,
        n_adv=n_adv,
        interpret=interpret,
    )


@register_backend("pallas")
class PallasBackend(Backend):
    min_batch_bucket = 256

    def _route_plan(self, tree, cache, tile_m, tile_l, interpret):
        sig = planlib.tree_signature(tree)
        cut_bucket = pad_bucket(tree.cuts.n_cuts, LANE)
        leaf_bucket = pad_bucket(tree.n_leaves, LANE)
        tile_l = min(tile_l, leaf_bucket)
        key = PlanKey(
            sig, "pallas", 0, 0, leaf_bucket, cut_bucket,
            ("route", tile_m, tile_l, interpret),
        )

        def build():
            packed = planlib.pack_route_constants(
                tree, cut_bucket, leaf_bucket
            )
            meta = {
                "n_adv": packed.pop("n_adv"),
                "n_cat_bits": packed.pop("n_cat_bits"),
                "tile_l": tile_l,
            }
            operands = {kk: jnp.asarray(v) for kk, v in packed.items()}
            fn = functools.partial(
                _route_pallas_padded,
                k=operands,
                tile_m=tile_m,
                tile_l=tile_l,
                n_cat_bits=meta["n_cat_bits"],
                n_adv=meta["n_adv"],
                interpret=interpret,
            )
            return CompiledPlan(key=key, fn=fn, operands=operands, meta=meta)

        return cache.get(key, build)

    def route(
        self, tree, cache, records, tile_m: int = 256, tile_l: int = LANE,
        interpret: bool | None = None, **opts,
    ):
        if interpret is None:
            interpret = interpret_default()
        plan = self._route_plan(tree, cache, tile_m, tile_l, interpret)
        m = records.shape[0]
        m_bucket = pad_bucket(m, max(self.min_batch_bucket, tile_m))
        if m_bucket % tile_m:  # non-power-of-two tile_m
            m_bucket = ((m_bucket + tile_m - 1) // tile_m) * tile_m
        padded = np.zeros((m_bucket, records.shape[1]), np.float32)
        padded[:m] = records
        bids = plan.fn(jnp.asarray(padded))
        return np.asarray(bids[:m]).astype(np.int32)

    def _ingest_plan(self, tree, cache, tile_m, tile_l, interpret):
        sig = planlib.tree_signature(tree)
        cut_bucket = pad_bucket(tree.cuts.n_cuts, LANE)
        leaf_bucket = pad_bucket(tree.n_leaves, LANE)
        from repro.kernels.fused_ingest import MAX_LEAF_BUCKET

        if leaf_bucket > MAX_LEAF_BUCKET:
            raise ValueError(
                f"the fused pallas kernel holds at most {MAX_LEAF_BUCKET} "
                f"(padded) leaves in VMEM; this tree has {tree.n_leaves} "
                f"(bucket {leaf_bucket}) — ingest it on backend='jax'"
            )
        tile_l = min(tile_l, leaf_bucket)
        if leaf_bucket % tile_l:  # non-divisor tile (autotuned oddball)
            tile_l = LANE
        key = PlanKey(
            sig, "pallas", 0, 0, leaf_bucket, cut_bucket,
            ("ingest", tile_m, tile_l, interpret),
        )

        def build():
            packed = planlib.pack_route_constants(
                tree, cut_bucket, leaf_bucket
            )
            meta = {
                "n_adv": packed.pop("n_adv"),
                "n_cat_bits": packed.pop("n_cat_bits"),
                "tile_l": tile_l,
            }
            operands = {kk: jnp.asarray(v) for kk, v in packed.items()}
            fn = functools.partial(
                _ingest_pallas_padded,
                k=operands,
                tile_m=tile_m,
                tile_l=tile_l,
                n_cat_bits=meta["n_cat_bits"],
                n_adv=meta["n_adv"],
                interpret=interpret,
            )
            return CompiledPlan(key=key, fn=fn, operands=operands, meta=meta)

        return cache.get(key, build)

    def fused_outputs(
        self, tree, cache, records, tile_m: int | None = None,
        tile_l: int | None = None, interpret: bool | None = None,
        return_bids: bool = True, **opts,
    ):
        m = records.shape[0]
        L = tree.n_leaves
        with spans.span(spans.ENGINE_STAGE):
            if interpret is None:
                interpret = interpret_default()
            if tile_m is None or tile_l is None:
                from repro.engine import autotune

                cfg = autotune.lookup("pallas", autotune.geometry_key(tree))
                if cfg is not None and cfg.interpret != interpret:
                    cfg = None  # tiles timed in the other mode say nothing
                tile_m = tile_m or (cfg.tile_m if cfg else 256)
                tile_l = tile_l or (cfg.tile_l if cfg else LANE)
            plan = self._ingest_plan(tree, cache, tile_m, tile_l, interpret)
            m_bucket = pad_bucket(m, max(self.min_batch_bucket, tile_m))
            if m_bucket % tile_m:  # non-power-of-two tile_m
                m_bucket = ((m_bucket + tile_m - 1) // tile_m) * tile_m
            padded = np.zeros((m_bucket, records.shape[1]), np.float32)
            padded[:m] = records
            valid = np.zeros((m_bucket, 1), np.float32)
            valid[:m] = 1.0
            bids, counts, *aggs = plan.fn(
                jnp.asarray(padded), jnp.asarray(valid)
            )
        with spans.span(spans.ENGINE_DEVICE_WAIT):
            aggs = (np.asarray(counts)[0, :L],) + tuple(
                np.asarray(a)[:L] for a in aggs
            )
            bids = (
                (np.asarray(bids)[:m, 0] - 1.0).astype(np.int32)
                if return_bids else None
            )
        return bids, aggs

    fold_outputs = JaxBackend.fold_outputs

    def query_hits(self, tree, cache, wt, interpret: bool | None = None,
                   **opts):
        from repro.kernels import ops

        hits, _ = ops.query_intersect(tree, wt, interpret=interpret)
        return hits
