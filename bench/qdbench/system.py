"""The program under test, reached through its entry points alone.

``LayoutService.build`` builds the layout, ``LayoutService.ingest``
streams rows into it, and ``QueryServer`` serves queries over it.  This
module turns the benchmark's plain tables and queries into the program's
types, and reads back what the program published: per-block counts, leaf
descriptions, served block lists and the server's counters.  Nothing here
names a backend: every call runs the service's defaults.

:class:`Program` is the one seam through which a run touches the
program, so a test or the control can put a variant in its place.
"""

from __future__ import annotations

import pathlib
import sys

import numpy as np

SRC = pathlib.Path(__file__).resolve().parents[2] / "src"


def import_program() -> None:
    """Make the program importable from the checkout's ``src``."""
    if not (SRC / "repro").is_dir():
        raise FileNotFoundError(f"the program is not in this checkout ({SRC})")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


class Program:
    """Adapter from the benchmark's plain data to the program's API."""

    def __init__(self):
        import_program()
        from repro.core import predicates as preds
        from repro.core import query as qry

        self._preds, self._qry = preds, qry
        self._op_code = {
            "<": preds.OP_LT, "<=": preds.OP_LE, ">": preds.OP_GT,
            ">=": preds.OP_GE, "==": preds.OP_EQ, "!=": preds.OP_NE,
        }
        self._op_name = {v: k for k, v in self._op_code.items()}

    # -- plain data -> program types ------------------------------------------
    def schema(self, schema):
        return self._preds.Schema(tuple(
            self._preds.Column(c.name, c.kind, c.dom) for c in schema
        ))

    def query(self, query):
        qry = self._qry

        def atom(a):
            if a[0] == "range":
                return qry.RangeAtom(a[1], self._op_code[a[2]], a[3])
            if a[0] == "in":
                return qry.InAtom(a[1], tuple(a[2]))
            return qry.AdvAtom(a[1], self._op_code[a[2]], a[3])

        return qry.Query(tuple(tuple(atom(a) for a in c) for c in query))

    def workload(self, schema, queries):
        return self._qry.Workload(
            self.schema(schema), tuple(self.query(q) for q in queries)
        )

    # -- the entry points ------------------------------------------------------
    def build(self, sample, schema, queries, cfg: dict, seed: int):
        """Greedy layout on ``sample`` for ``queries`` (the config's build
        workload), candidate cuts from the same workload."""
        from repro.service import LayoutService

        work = self.workload(schema, queries)
        return LayoutService.build(
            sample, work, strategy=cfg["strategy"],
            cuts=work.candidate_cuts(max_adv=cfg["max_adv"]),
            min_block=cfg["min_block"], seed=seed,
        )

    def ingest(self, svc, batches):
        """One ``LayoutService.ingest`` call over an iterable of batches.

        Returns the per-block row counts the call published."""
        return np.asarray(svc.ingest(batches).block_sizes)

    def server(self, svc):
        from repro.serve import QueryServer

        return QueryServer(svc).start()

    def warm_server(self, server, schema, queries) -> None:
        """Compile the server's query plans for every dispatch size."""
        server.warm(self.workload(schema, queries))

    def submit(self, server, query):
        """Submit one program query; returns its ticket, or None when the
        server refused it at the door."""
        from repro.serve.coalescer import AdmissionError

        try:
            return server.submit(query)
        except AdmissionError:
            return None

    @staticmethod
    def outcome(ticket, timeout: float):
        """The block ids of a ticket's answer, once it has one; ``None``
        for a ticket that failed or did not finish in time.  The benchmark
        times the answer on its own clock."""
        try:
            return np.asarray(ticket.result(timeout=timeout).bids)
        except Exception:  # noqa: BLE001 - a lost request, counted as such
            return None

    @staticmethod
    def counters(server) -> dict:
        return server.counters.as_dict()

    # -- what the program published ---------------------------------------------
    @staticmethod
    def descriptions(svc) -> dict:
        t = svc.tree
        return {
            "lo": t.leaf_lo.copy(), "hi": t.leaf_hi.copy(),
            "cat": t.leaf_cat.copy(), "adv": t.leaf_adv.copy(),
        }

    def layout(self, svc, schema) -> dict:
        """Plain snapshot of the layout's topology and cut table."""
        t = svc.tree
        cuts = t.cuts
        kinds = {self._preds.KIND_RANGE: "range", self._preds.KIND_IN: "in",
                 self._preds.KIND_ADV: "adv"}
        return {
            "n_leaves": int(t.n_leaves),
            "depth": int(t.depth),
            "node_cut": np.asarray(t.cut_id, np.int64).copy(),
            "node_left": np.asarray(t.left, np.int64).copy(),
            "node_right": np.asarray(t.right, np.int64).copy(),
            "node_leaf": np.asarray(t.leaf_bid, np.int64).copy(),
            "cut_kind": np.array([kinds[int(k)] for k in cuts.kind]),
            "cut_dim": np.maximum(np.asarray(cuts.dim, np.int64), 0),
            "cut_point": np.asarray(cuts.cutpoint, np.int64).copy(),
            "cut_mask": np.asarray(cuts.in_mask, bool).copy(),
            "cut_adv": np.asarray(cuts.adv_id, np.int64).copy(),
            "adv": [(int(a.col_a), self._op_name[int(a.op)], int(a.col_b))
                    for a in cuts.adv],
            "cat_offsets": schema.cat_offsets,
        }
