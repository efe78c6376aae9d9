"""Compiled-plan cache for the LayoutEngine (ROADMAP: caching/multi-backend).

A *plan* is everything a backend needs to route/intersect against one frozen
tree at one padded geometry: the packed device operands plus a callable whose
jit/Pallas compilation is reused across calls.  Plans are keyed by

    (tree signature, backend, batch bucket, node bucket,
     leaf bucket, cut bucket, backend options)

where every size is rounded up to a power-of-two *padding bucket*, so online
ingestion of varying batch sizes hits the same compiled executable instead of
retracing per shape.  Tree signatures are identity tokens: routing operands
depend only on the frozen topology (immutable), while query operands also
depend on the leaf descriptions, which ``tighten`` mutates — those plans key
on a description version that ``tighten`` bumps.

Trace counters (`trace_counts`) increment inside the jitted entry points at
*trace* time only, so benchmarks and tests can assert that a warm cache
performs zero recompilation.
"""

from __future__ import annotations

# qdlint: deterministic-module

import dataclasses
import hashlib
import itertools
import threading
from collections import Counter
from typing import Any, Callable, Hashable

import numpy as np

from repro.core.predicates import CutTable
from repro.core.qdtree import FrozenQdTree

LANE = 128  # TPU lane width; leaf/cut buckets must be multiples of this

_SIG_COUNTER = itertools.count()  # guarded by: _SIG_LOCK
_SIG_LOCK = threading.Lock()

TRACE_COUNTS: Counter = Counter()


def count_trace(name: str) -> None:
    """Called from inside jitted bodies — runs once per (re)trace."""
    TRACE_COUNTS[name] += 1


def trace_counts() -> dict[str, int]:
    return dict(TRACE_COUNTS)


def trace_delta(before: dict[str, int], after: dict[str, int]) -> dict:
    """Counters that moved between two ``trace_counts`` snapshots."""
    return {
        k: after.get(k, 0) - before.get(k, 0)
        for k in sorted(set(before) | set(after))
        if after.get(k, 0) != before.get(k, 0)
    }


def pad_bucket(n: int, minimum: int = 1) -> int:
    """Smallest power of two ≥ max(n, minimum)."""
    target = max(int(n), int(minimum), 1)
    return 1 << (target - 1).bit_length()


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    import jax

    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Pallas kernels run in interpret mode wherever there is no TPU."""
    return not on_tpu()


def tree_signature(tree: FrozenQdTree) -> int:
    """Stable per-object token (frozen topology is immutable)."""
    sig = getattr(tree, "_engine_sig", None)
    if sig is None:
        with _SIG_LOCK:
            sig = getattr(tree, "_engine_sig", None)
            if sig is None:
                sig = next(_SIG_COUNTER)
                object.__setattr__(tree, "_engine_sig", sig)
    return sig


def desc_version(tree: FrozenQdTree) -> int:
    """Leaf-description version; ``FrozenQdTree.tighten`` bumps it."""
    return getattr(tree, "_desc_version", 0)


def cuts_signature(cuts: CutTable) -> int:
    """Content hash of a cut table (plus its schema), cached on the object.

    Unlike :func:`tree_signature` (an identity token), this is a *content*
    signature: two generations whose trees were built from equal cut tables
    share it, so workload tensorizations (which depend only on schema +
    cuts) survive a hot swap (ROADMAP: workload-tensor reuse).
    """
    sig = getattr(cuts, "_cuts_sig", None)
    if sig is None:
        h = hashlib.blake2b(digest_size=8)
        for a in (cuts.kind, cuts.dim, cuts.cutpoint, cuts.in_mask,
                  cuts.adv_id):
            h.update(np.ascontiguousarray(a).tobytes())
        h.update(repr(tuple(
            (a.col_a, a.op, a.col_b) for a in cuts.adv
        )).encode())
        h.update(repr(tuple(
            (c.name, c.kind, c.dom) for c in cuts.schema.columns
        )).encode())
        sig = int.from_bytes(h.digest(), "little")
        object.__setattr__(cuts, "_cuts_sig", sig)
    return sig


@dataclasses.dataclass(frozen=True)
class PlanKey:
    sig: int
    backend: str
    m_bucket: int
    node_bucket: int
    leaf_bucket: int
    cut_bucket: int
    opts: tuple[Hashable, ...] = ()


@dataclasses.dataclass
class CompiledPlan:
    """A backend-ready routing/intersection plan.

    ``operands`` are device-resident packed arrays; ``fn`` closes over them
    and accepts the padded batch.  ``meta`` carries static sizes the caller
    needs to slice padding back off.
    """

    key: PlanKey
    fn: Callable[..., Any]
    operands: dict
    meta: dict


class PlanCache:
    """Keyed plan store with hit/miss accounting (thread-safe)."""

    def __init__(self):
        self._plans: dict[Any, Any] = {}  # guarded by: self._lock
        self._lock = threading.Lock()
        self.hits = 0  # guarded by: self._lock
        self.misses = 0  # guarded by: self._lock

    def get(self, key: Any, builder: Callable[[], Any]) -> Any:
        with self._lock:
            if key in self._plans:
                self.hits += 1
                return self._plans[key]
        # build outside the lock (builders may trigger compilation)
        plan = builder()
        with self._lock:
            self.misses += 1
            self._plans.setdefault(key, plan)
            return self._plans[key]

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)

    def keys(self) -> list:
        with self._lock:
            return list(self._plans)

    def evict(self, predicate: Callable[[Any], bool]) -> int:
        """Drop every entry whose key satisfies ``predicate``."""
        with self._lock:
            stale = [k for k in self._plans if predicate(k)]
            for k in stale:
                del self._plans[k]
            return len(stale)

    def stats(self) -> dict:
        # len(self._plans) inlined: __len__ takes this same non-reentrant lock
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "size": len(self._plans),
            }


# ---------------------------------------------------------------------------
# Operand packing (host side) — formerly scattered across
# core/routing.tree_arrays and kernels/ops.route_constants, now built once
# per (tree, bucket geometry) and owned by cached plans.
# ---------------------------------------------------------------------------
def pack_tree_arrays(tree: FrozenQdTree, node_bucket: int) -> dict:
    """Padded flat-tree arrays for the jnp descent backend (numpy, host)."""
    n = tree.n_nodes
    if node_bucket < n:
        raise ValueError("node_bucket < n_nodes")

    def _pad(x: np.ndarray, fill) -> np.ndarray:
        out = np.full((node_bucket,) + x.shape[1:], fill, x.dtype)
        out[:n] = x
        return out

    return {
        "cut_id": _pad(tree.cut_id, -1),
        "left": _pad(tree.left, 0),
        "right": _pad(tree.right, 0),
        "leaf_bid": _pad(tree.leaf_bid, -1),
    }


def pack_cut_arrays(tree: FrozenQdTree, cut_bucket: int) -> dict:
    """Padded cut-table arrays for jnp predicate evaluation (numpy, host).

    Padded cut columns are never consulted by the descent (internal nodes
    reference only real cut ids), so their values are arbitrary-but-fixed.
    """
    cuts = tree.cuts
    n = cuts.n_cuts
    if cut_bucket < n:
        raise ValueError("cut_bucket < n_cuts")

    def _pad1(x: np.ndarray, fill) -> np.ndarray:
        out = np.full((cut_bucket,) + x.shape[1:], fill, x.dtype)
        out[:n] = x
        return out

    adv = np.array(
        [(a.col_a, a.op, a.col_b) for a in cuts.adv], np.int32
    ).reshape(-1, 3)
    return {
        "kind": _pad1(cuts.kind, -1),
        "dim": _pad1(np.maximum(cuts.dim, 0), 0),
        "cutpoint": _pad1(cuts.cutpoint, 0),
        "in_mask": _pad1(cuts.in_mask, False),
        "adv_id": _pad1(np.maximum(cuts.adv_id, 0), 0),
        "adv": adv,
        "cat_offset": np.maximum(cuts.schema.cat_offsets, 0),
    }


def path_matrices(tree: FrozenQdTree) -> tuple[np.ndarray, np.ndarray]:
    """PathPos/PathNeg (n_cuts, n_leaves): leaf path constraints."""
    n_cuts = tree.cuts.n_cuts
    pos = np.zeros((n_cuts, tree.n_leaves), np.float32)
    neg = np.zeros((n_cuts, tree.n_leaves), np.float32)
    stack: list[tuple[int, list[tuple[int, bool]]]] = [(0, [])]
    while stack:
        node, cons = stack.pop()
        bid = int(tree.leaf_bid[node])
        if bid >= 0:
            for c, d in cons:
                (pos if d else neg)[c, bid] = 1.0
        else:
            c = int(tree.cut_id[node])
            stack.append((int(tree.left[node]), cons + [(c, True)]))
            stack.append((int(tree.right[node]), cons + [(c, False)]))
    return pos, neg


def pack_route_constants(
    tree: FrozenQdTree, cut_bucket: int, leaf_bucket: int
) -> dict:
    """Dense Pallas-kernel operands at a padded geometry (numpy, host).

    ``cut_bucket``/``leaf_bucket`` must be LANE multiples ≥ the tree's
    actual counts (power-of-two buckets ≥ LANE satisfy this).
    """
    cuts, schema = tree.cuts, tree.schema
    if cut_bucket % LANE or leaf_bucket % LANE:
        raise ValueError("buckets must be LANE multiples")
    if cut_bucket < cuts.n_cuts or leaf_bucket < tree.n_leaves:
        raise ValueError("bucket smaller than tree geometry")
    d = schema.ndims
    c_pad, l_pad = cut_bucket, leaf_bucket
    dim_onehot = np.zeros((d, c_pad), np.float32)
    valid = np.arange(cuts.n_cuts)
    dim_onehot[np.maximum(cuts.dim, 0), valid] = (
        cuts.kind != 2
    ).astype(np.float32)[valid]
    cutpoint = np.zeros((1, c_pad), np.float32)
    cutpoint[0, : cuts.n_cuts] = cuts.cutpoint
    bits = max(schema.total_cat_bits, 1)
    b_pad = max(((bits + LANE - 1) // LANE) * LANE, LANE)
    in_mask_t = np.zeros((b_pad, c_pad), np.float32)
    in_mask_t[: cuts.in_mask.shape[1], : cuts.n_cuts] = (
        cuts.in_mask.T.astype(np.float32)
    )
    is_cat = schema.is_categorical.astype(np.float32)[None, :]
    cat_off = np.maximum(schema.cat_offsets, 0).astype(np.float32)[None, :]
    n_adv = cuts.n_adv
    a3 = max(n_adv, 1)
    adv_cols = np.zeros((a3, 3), np.float32)
    adv_sel = np.zeros((a3, c_pad), np.float32)
    for j, a in enumerate(cuts.adv):
        adv_cols[j] = (a.col_a, a.op, a.col_b)
    advc = np.nonzero(cuts.kind == 2)[0]
    adv_sel[cuts.adv_id[advc], advc] = 1.0
    kind = np.zeros((1, c_pad), np.float32)
    kind[0, : cuts.n_cuts] = cuts.kind

    pos, neg = path_matrices(tree)
    pos = np.pad(pos, ((0, c_pad - pos.shape[0]), (0, 0)))
    neg = np.pad(neg, ((0, c_pad - neg.shape[0]), (0, 0)))
    leafid = np.zeros((1, l_pad), np.float32)
    leafid[0, : tree.n_leaves] = np.arange(tree.n_leaves) + 1.0
    pos = np.pad(pos, ((0, 0), (0, l_pad - pos.shape[1])))
    neg = np.pad(neg, ((0, 0), (0, l_pad - neg.shape[1])))
    # padded leaf columns must always register ≥1 violation: require cut 0
    # both true and false
    pos[0, tree.n_leaves :] = 1.0
    neg[0, tree.n_leaves :] = 1.0

    return dict(
        dim_onehot=dim_onehot,
        cutpoint=cutpoint,
        in_mask_t=in_mask_t,
        is_cat=is_cat,
        cat_off=cat_off,
        adv_cols=adv_cols,
        adv_sel=adv_sel,
        kind=kind,
        pathpos=pos,
        pathneg=neg,
        leafid=leafid,
        n_adv=n_adv,
        n_cat_bits=b_pad,
    )


def pack_leaf_descs(
    tree: FrozenQdTree, leaf_bucket: int
) -> dict:
    """Padded leaf-description arrays for query intersection backends."""
    L = tree.n_leaves
    if leaf_bucket < L:
        raise ValueError("leaf_bucket < n_leaves")

    def _pad(x: np.ndarray, fill) -> np.ndarray:
        out = np.full((leaf_bucket,) + x.shape[1:], fill, x.dtype)
        out[:L] = x
        return out

    return {
        "leaf_lo": _pad(tree.leaf_lo, 0),
        "leaf_hi": _pad(tree.leaf_hi, 0),  # empty box ⇒ padded leaves miss
        "leaf_cat": _pad(tree.leaf_cat, False),
        "leaf_adv": _pad(tree.leaf_adv, False),
    }
