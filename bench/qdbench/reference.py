"""The plain reference: what a qd-tree layout must answer, in numpy.

It imports nothing of the program.  Its inputs are the benchmark's own
table and queries, and the layout as a plain snapshot taken right after
the build (:func:`qdbench.system.Program.layout`): node cuts, children
and leaf ids, and the cut table.  It first checks that snapshot on its
own (:func:`layout_faults`): a sound tree over the schema, every block
reached once.  From it it then computes, independently of the code under
test:

* which block each row belongs to, by splitting the rows at every node
  by that node's cut, from the root down (paper Sec 3.1);
* each block's row count and tight description: per-column [min, max+1),
  the categorical codes present, and which truth values of each advanced
  (column-versus-column) cut occur (Sec 3.2).  An empty block has the
  all-zero description, which no query intersects;
* the blocks a query must scan: those whose description intersects some
  conjunct of the query (Sec 3.3).  An advanced atom constrains a block
  only where its comparison is one of the layout's advanced cuts;
* the rows a query selects, by a full-table filter.
"""

from __future__ import annotations

import numpy as np

OPS = {
    "<": np.less, "<=": np.less_equal, ">": np.greater,
    ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal,
}


def columns(records: np.ndarray) -> np.ndarray:
    """The table column by column, ``(cols, rows)``: the reference reads
    whole columns, which a row-major table strides through."""
    return np.ascontiguousarray(records.T)


def layout_faults(layout: dict, schema) -> list:
    """What is wrong with a layout snapshot, checked before the reference
    uses it: empty for a tree whose nodes are each reached once from the
    root, whose leaf ids are 0..n_leaves-1, each once, whose depth is the
    one stated, and whose cuts lie inside the schema (a range cut's point
    inside a numeric column's domain, an IN cut's codes inside its
    categorical column's segment, an advanced cut over two columns)."""
    faults = []
    node_cut, n_cuts = layout["node_cut"], len(layout["cut_kind"])
    n_nodes = len(node_cut)
    seen = np.zeros(n_nodes, bool)
    leaves, used, depth = [], set(), 0
    todo = [(0, 0)]
    while todo:
        node, level = todo.pop()
        if not 0 <= node < n_nodes or seen[node]:
            faults.append(f"node {node} out of range or reached twice")
            continue
        seen[node] = True
        cut = int(node_cut[node])
        if cut < 0:
            leaves.append(int(layout["node_leaf"][node]))
            depth = max(depth, level)
        elif cut >= n_cuts:
            faults.append(f"node {node} names cut {cut} of {n_cuts}")
        else:
            used.add(cut)
            todo.append((int(layout["node_left"][node]), level + 1))
            todo.append((int(layout["node_right"][node]), level + 1))
    if sorted(leaves) != list(range(layout["n_leaves"])):
        faults.append("leaf ids are not 0..n_leaves-1, each reached once")
    if depth != layout["depth"]:
        faults.append(f"depth {layout['depth']} stated, {depth} walked")
    off, n_dims = schema.cat_offsets, len(schema)
    for cut in sorted(used):
        kind, d = layout["cut_kind"][cut], int(layout["cut_dim"][cut])
        if kind == "adv":
            j = int(layout["cut_adv"][cut])
            sound = 0 <= j < len(layout["adv"]) and all(
                0 <= c < n_dims for c in layout["adv"][j][::2])
        elif not 0 <= d < n_dims:
            sound = False
        elif kind == "range":
            sound = (schema[d].kind == "numeric"
                     and 0 <= layout["cut_point"][cut] <= schema[d].dom)
        else:
            mask = layout["cut_mask"][cut]
            seg = slice(off[d], off[d] + schema[d].dom)
            sound = (schema[d].kind == "categorical"
                     and not np.delete(mask, np.arange(mask.size)[seg]).any())
        if not sound:
            faults.append(f"cut {cut} ({kind} on column {d}) lies outside "
                          f"the schema")
    return faults


def route(layout: dict, cols: np.ndarray) -> np.ndarray:
    """Block id of every row: split the rows at each node by that node's
    cut, from the root down, until each group stands on a leaf."""
    kind, dim, point = layout["cut_kind"], layout["cut_dim"], layout["cut_point"]
    off = layout["cat_offsets"]
    leaf = np.empty(cols.shape[1], np.int64)
    todo = [(0, np.arange(cols.shape[1]))]
    while todo:
        node, rows = todo.pop()
        cut = layout["node_cut"][node]
        if cut < 0:
            leaf[rows] = layout["node_leaf"][node]
            continue
        if kind[cut] == "range":
            truth = cols[dim[cut]][rows] < point[cut]
        elif kind[cut] == "in":
            seg = layout["cut_mask"][cut, off[dim[cut]]:]
            truth = seg[cols[dim[cut]][rows]]
        else:
            a, op, b = layout["adv"][layout["cut_adv"][cut]]
            truth = OPS[op](cols[a][rows], cols[b][rows])
        todo.append((layout["node_left"][node], rows[truth]))
        todo.append((layout["node_right"][node], rows[~truth]))
    return leaf


def describe(layout: dict, schema, cols: np.ndarray,
             leaf: np.ndarray) -> dict:
    """Row counts and tight descriptions of every block."""
    n_leaves = layout["n_leaves"]
    counts = np.bincount(leaf, minlength=n_leaves).astype(np.int64)
    full = counts > 0
    lo = np.zeros((n_leaves, cols.shape[0]), np.int64)
    hi = np.zeros_like(lo)
    if cols.shape[1]:
        order = np.argsort(leaf, kind="stable")
        at = np.concatenate([[0], np.cumsum(counts)[:-1]])[full]
        for d in range(cols.shape[0]):
            col = cols[d][order]
            lo[full, d] = np.minimum.reduceat(col, at)
            hi[full, d] = np.maximum.reduceat(col, at) + 1
    cat = np.zeros((n_leaves, max(schema.cat_bits, 1)), bool)
    off = schema.cat_offsets
    for d in np.nonzero(schema.is_cat)[0]:
        dom = schema[d].dom
        seen = np.bincount(leaf * dom + cols[d], minlength=n_leaves * dom)
        cat[:, off[d]:off[d] + dom] = seen.reshape(n_leaves, dom) > 0
    adv = np.zeros((n_leaves, len(layout["adv"]), 2), bool)
    for j, (a, op, b) in enumerate(layout["adv"]):
        t = OPS[op](cols[a], cols[b])
        adv[:, j, 0] = np.bincount(leaf[t], minlength=n_leaves) > 0
        adv[:, j, 1] = np.bincount(leaf[~t], minlength=n_leaves) > 0
    return {"counts": counts, "lo": lo, "hi": hi, "cat": cat, "adv": adv}


def _conjunct_box(schema, conj, adv_index):
    """One conjunct as a numeric box, allowed codes and required cuts."""
    qlo = np.zeros(len(schema), np.int64)
    qhi = schema.doms.copy()
    qcat = np.ones(max(schema.cat_bits, 1), bool)
    need = []
    off = schema.cat_offsets
    for atom in conj:
        if atom[0] == "range":
            _, d, op, v = atom
            if op == "<":
                qhi[d] = min(qhi[d], v)
            elif op == "<=":
                qhi[d] = min(qhi[d], v + 1)
            elif op == ">":
                qlo[d] = max(qlo[d], v + 1)
            elif op == ">=":
                qlo[d] = max(qlo[d], v)
            else:
                raise ValueError(f"range op {op!r}")
        elif atom[0] == "in":
            _, d, values = atom
            seg = np.zeros(schema[d].dom, bool)
            seg[list(values)] = True
            qcat[off[d]: off[d] + schema[d].dom] &= seg
        else:
            # a comparison that is none of the layout's advanced cuts has
            # no bit in the descriptions, so it cannot rule a block out
            j = adv_index.get(tuple(atom[1:]))
            if j is not None:
                need.append(j)
    return qlo, qhi, qcat, need


def query_blocks(desc: dict, schema, layout: dict, query) -> np.ndarray:
    """Sorted ids of the blocks whose description intersects ``query``."""
    adv_index = {tuple(a): j for j, a in enumerate(layout["adv"])}
    numeric = ~schema.is_cat
    off = schema.cat_offsets
    hit = np.zeros(desc["lo"].shape[0], bool)
    for conj in query:
        qlo, qhi, qcat, need = _conjunct_box(schema, conj, adv_index)
        ok = (np.maximum(desc["lo"], qlo) < np.minimum(desc["hi"], qhi))[
            :, numeric].all(axis=1)
        for d in np.nonzero(schema.is_cat)[0]:
            seg = slice(off[d], off[d] + schema[d].dom)
            ok &= (desc["cat"][:, seg] & qcat[seg]).any(axis=1)
        for j in need:
            ok &= desc["adv"][:, j, 0]
        hit |= ok
    return np.nonzero(hit)[0]


def query_rows(cols: np.ndarray, schema, query) -> np.ndarray:
    """(rows,) bool: the rows ``query`` selects, by a full-table filter."""
    out = np.zeros(cols.shape[1], bool)
    for conj in query:
        acc = np.ones(cols.shape[1], bool)
        for atom in conj:
            if atom[0] == "range":
                acc &= OPS[atom[2]](cols[atom[1]], atom[3])
            elif atom[0] == "in":
                allowed = np.zeros(schema[atom[1]].dom, bool)
                allowed[list(atom[2])] = True
                acc &= allowed[cols[atom[1]]]
            else:
                acc &= OPS[atom[2]](cols[atom[1]], cols[atom[3]])
        out |= acc
    return out
