"""Tables and queries of the benchmark's configurations, from a seed.

These are the benchmark's own copies of the repository's TPC-H-like
generator and query templates (the qd-tree paper,
arXiv:2004.10898, Sec 7.1-7.2): the same column families, domains,
correlations and selectivities, in plain Python and numpy.  A later change
to the program's generators does not move the yardstick.

A schema is a tuple of :class:`Col`.  A query is a DNF: a tuple of
conjuncts, each a tuple of atoms, where an atom is one of

    ("range", dim, op, literal)   op in "<", "<=", ">", ">="
    ("in", dim, values)           values a sorted tuple of codes
    ("adv", dim_a, op, dim_b)     column-versus-column comparison

Tables are dictionary-encoded int32 matrices, one column per schema entry.
"""

from __future__ import annotations

import dataclasses

import numpy as np

DATE_DOM = 2526  # days in TPC-H's 1992-01-01 .. 1998-12-01 span
YEAR = 365


@dataclasses.dataclass(frozen=True)
class Col:
    name: str
    kind: str  # "numeric" | "categorical"
    dom: int  # codes live in [0, dom)


class Schema(tuple):
    """A tuple of :class:`Col` with the lookups the reference needs."""

    def dim(self, name: str) -> int:
        for i, c in enumerate(self):
            if c.name == name:
                return i
        raise KeyError(name)

    @property
    def doms(self) -> np.ndarray:
        return np.array([c.dom for c in self], np.int64)

    @property
    def is_cat(self) -> np.ndarray:
        return np.array([c.kind == "categorical" for c in self], bool)

    @property
    def cat_offsets(self) -> np.ndarray:
        """Offset of each categorical column in the concatenated bit space
        (-1 for numeric columns)."""
        off = np.full(len(self), -1, np.int64)
        pos = 0
        for i, c in enumerate(self):
            if c.kind == "categorical":
                off[i] = pos
                pos += c.dom
        return off

    @property
    def cat_bits(self) -> int:
        return int(sum(c.dom for c in self if c.kind == "categorical"))


# ---------------------------------------------------------------------------
# TPC-H lineitem, denormalized to the columns the templates touch
# ---------------------------------------------------------------------------
def tpch_schema() -> Schema:
    N, C = "numeric", "categorical"
    return Schema((
        Col("l_shipdate", N, DATE_DOM),
        Col("l_commitdate", N, DATE_DOM),
        Col("l_receiptdate", N, DATE_DOM),
        Col("l_quantity", N, 51),
        Col("l_discount", N, 11),
        Col("l_extendedprice", N, 10000),
        Col("o_orderdate", N, DATE_DOM),
        Col("o_totalprice", N, 10000),
        Col("p_size", N, 51),
        Col("p_retailprice", N, 2000),
        Col("l_shipmode", C, 7),
        Col("l_shipinstruct", C, 4),
        Col("l_returnflag", C, 3),
        Col("l_linestatus", C, 2),
        Col("p_brand", C, 25),
        Col("p_container", C, 40),
        Col("c_mktsegment", C, 5),
        Col("r_name", C, 5),
        Col("o_orderpriority", C, 5),
        Col("c_nationkey", C, 25),
        Col("s_nationkey", C, 25),
    ))


def make_tpch(n: int, seed: int) -> np.ndarray:
    """Uniform TPC-H-style rows with the date correlations that make the
    advanced cuts (commit < receipt, ship < commit) selective."""
    rng = np.random.default_rng(seed)
    ship = rng.integers(0, DATE_DOM - 120, n)
    commit = np.clip(ship + rng.integers(-30, 60, n), 0, DATE_DOM - 1)
    receipt = np.clip(ship + rng.integers(1, 31, n), 0, DATE_DOM - 1)
    orderdate = np.clip(ship - rng.integers(1, 121, n), 0, DATE_DOM - 1)
    cols = [
        ship, commit, receipt,
        rng.integers(1, 51, n),  # quantity
        rng.integers(0, 11, n),  # discount
        rng.integers(0, 10000, n),  # extendedprice
        orderdate,
        rng.integers(0, 10000, n),  # totalprice
        rng.integers(1, 51, n),  # p_size
        rng.integers(0, 2000, n),  # retailprice
        rng.integers(0, 7, n),  # shipmode
        rng.integers(0, 4, n),  # shipinstruct
        rng.integers(0, 3, n),  # returnflag
        rng.integers(0, 2, n),  # linestatus
        rng.integers(0, 25, n),  # brand
        rng.integers(0, 40, n),  # container
        rng.integers(0, 5, n),  # mktsegment
        rng.integers(0, 5, n),  # r_name
        rng.integers(0, 5, n),  # orderpriority
        rng.integers(0, 25, n),  # c_nationkey
        rng.integers(0, 25, n),  # s_nationkey
    ]
    return np.stack(cols, axis=1).astype(np.int32)


def _date_range(rng, span: int) -> tuple[int, int]:
    lo = int(rng.integers(0, DATE_DOM - span))
    return lo, lo + span


def _in(dim: int, values) -> tuple:
    return ("in", dim, tuple(sorted({int(v) for v in values})))


def tpch_templates(schema: Schema) -> dict:
    """The 15 TPC-H templates that reach the denormalized lineitem table:
    name -> ``fn(rng) -> query``."""
    d = schema.dim

    def q1(rng):
        cutoff = int(rng.integers(DATE_DOM - 120, DATE_DOM - 1))
        return ((("range", d("l_shipdate"), "<=", cutoff),),)

    def q3(rng):
        day = int(rng.integers(YEAR, DATE_DOM - YEAR))
        return ((
            _in(d("c_mktsegment"), (int(rng.integers(0, 5)),)),
            ("range", d("o_orderdate"), "<", day),
            ("range", d("l_shipdate"), ">", day),
        ),)

    def q4(rng):
        lo, hi = _date_range(rng, 90)
        return ((
            ("range", d("o_orderdate"), ">=", lo),
            ("range", d("o_orderdate"), "<", hi),
            ("adv", d("l_commitdate"), "<", d("l_receiptdate")),
        ),)

    def q5(rng):
        lo, hi = _date_range(rng, YEAR)
        return ((
            _in(d("r_name"), (int(rng.integers(0, 5)),)),
            ("range", d("o_orderdate"), ">=", lo),
            ("range", d("o_orderdate"), "<", hi),
        ),)

    def q6(rng):
        lo, hi = _date_range(rng, YEAR)
        disc = int(rng.integers(1, 9))
        return ((
            ("range", d("l_shipdate"), ">=", lo),
            ("range", d("l_shipdate"), "<", hi),
            ("range", d("l_discount"), ">=", disc - 1),
            ("range", d("l_discount"), "<=", disc + 1),
            ("range", d("l_quantity"), "<", int(rng.integers(24, 26))),
        ),)

    def q7(rng):
        a, b = rng.choice(25, size=2, replace=False)
        lo, hi = _date_range(rng, 2 * YEAR)
        return ((
            _in(d("c_nationkey"), (a, b)),
            _in(d("s_nationkey"), (a, b)),
            ("range", d("l_shipdate"), ">=", lo),
            ("range", d("l_shipdate"), "<", hi),
        ),)

    def q8(rng):
        lo, hi = _date_range(rng, 2 * YEAR)
        return ((
            _in(d("r_name"), (int(rng.integers(0, 5)),)),
            ("range", d("o_orderdate"), ">=", lo),
            ("range", d("o_orderdate"), "<", hi),
            _in(d("p_brand"), rng.choice(25, 3, replace=False)),
        ),)

    def q9(rng):
        return ((
            _in(d("s_nationkey"), rng.choice(25, 4, replace=False)),
            _in(d("p_container"), rng.choice(40, 8, replace=False)),
        ),)

    def q10(rng):
        lo, hi = _date_range(rng, 90)
        return ((
            _in(d("l_returnflag"), (2,)),
            ("range", d("o_orderdate"), ">=", lo),
            ("range", d("o_orderdate"), "<", hi),
        ),)

    def q12(rng):
        lo, hi = _date_range(rng, YEAR)
        modes = rng.choice(7, size=2, replace=False)
        return ((
            _in(d("l_shipmode"), modes),
            ("adv", d("l_commitdate"), "<", d("l_receiptdate")),
            ("adv", d("l_shipdate"), "<", d("l_commitdate")),
            ("range", d("l_receiptdate"), ">=", lo),
            ("range", d("l_receiptdate"), "<", hi),
        ),)

    def q14(rng):
        lo, hi = _date_range(rng, 30)
        return ((
            ("range", d("l_shipdate"), ">=", lo),
            ("range", d("l_shipdate"), "<", hi),
        ),)

    def q17(rng):
        return ((
            _in(d("p_brand"), (int(rng.integers(0, 25)),)),
            _in(d("p_container"), (int(rng.integers(0, 40)),)),
            ("range", d("l_quantity"), "<", int(rng.integers(3, 8))),
        ),)

    def q18(rng):
        return ((("range", d("l_quantity"), ">", int(rng.integers(44, 49))),),)

    def q19(rng):  # OR of three 8-filter conjunctions
        def arm(brands, containers, qlo, size_hi):
            q = int(rng.integers(*qlo))
            return (
                _in(d("p_brand"), (int(rng.choice(brands)),)),
                _in(d("p_container"), containers),
                ("range", d("l_quantity"), ">=", q),
                ("range", d("l_quantity"), "<=", q + 10),
                ("range", d("p_size"), ">=", 1),
                ("range", d("p_size"), "<=", size_hi),
                _in(d("l_shipinstruct"), (0,)),
                _in(d("l_shipmode"), (0, 1)),
            )

        return (
            arm(range(0, 8), rng.choice(40, 4, replace=False), (1, 11), 5),
            arm(range(8, 16), rng.choice(40, 4, replace=False), (10, 20), 10),
            arm(range(16, 25), rng.choice(40, 4, replace=False), (20, 30), 15),
        )

    def q21(rng):
        return ((
            ("adv", d("l_receiptdate"), ">", d("l_commitdate")),
            _in(d("s_nationkey"), (int(rng.integers(0, 25)),)),
            _in(d("l_linestatus"), (0,)),
        ),)

    return {
        "q1": q1, "q3": q3, "q4": q4, "q5": q5, "q6": q6, "q7": q7,
        "q8": q8, "q9": q9, "q10": q10, "q12": q12, "q14": q14,
        "q17": q17, "q18": q18, "q19": q19, "q21": q21,
    }


def tpch_queries(schema: Schema, per_template: int, seed: int) -> list:
    """``per_template`` instances of every template, template by template."""
    rng = np.random.default_rng(seed)
    return [
        fn(rng)
        for fn in tpch_templates(schema).values()
        for _ in range(per_template)
    ]


# ---------------------------------------------------------------------------
# lookup by the names the configuration files use
# ---------------------------------------------------------------------------
TABLES = {
    "tpch": (tpch_schema, make_tpch),
}
TEMPLATES = {"tpch": tpch_templates}


def make_table(generator: str, n: int, seed: int) -> tuple[Schema, np.ndarray]:
    schema_fn, make = TABLES[generator]
    return schema_fn(), make(n, seed)


def make_queries(family: str, schema: Schema, n: int, seed: int) -> list:
    """``n`` queries of a family; for ``tpch``, ``n / 15`` instances of
    each template, template by template."""
    if family == "tpch":
        per, rest = divmod(n, len(tpch_templates(schema)))
        if rest:
            raise ValueError(f"tpch query count {n} is not a multiple of 15")
        return tpch_queries(schema, per, seed)
    raise KeyError(family)


def template_names(family: str, schema: Schema) -> list:
    """Names of a family's templates; only ``tpch`` has templates."""
    return list(TEMPLATES[family](schema))


def instance(family: str, schema: Schema, template: str, rng) -> tuple:
    """One fresh instance of ``template`` drawn with ``rng``."""
    return TEMPLATES[family](schema)[template](rng)
