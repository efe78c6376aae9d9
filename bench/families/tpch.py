"""The 15 TPC-H templates that reach the denormalized lineitem table of
``tables/tpch.py`` (the qd-tree paper, arXiv:2004.10898, Sec 7.2): the
same predicates and selectivities as the repository's TPC-H query
templates, in plain Python."""

from __future__ import annotations

import numpy as np

from qdbench.datagen import Schema, in_atom

MULTIPLE = 15  # build queries come n / 15 to a template
YEAR = 365


def templates(schema: Schema) -> dict:
    """Name -> ``fn(rng) -> query``."""
    d = schema.dim
    days = schema[d("l_shipdate")].dom

    def date_range(rng, span: int) -> tuple[int, int]:
        lo = int(rng.integers(0, days - span))
        return lo, lo + span

    def q1(rng):
        cutoff = int(rng.integers(days - 120, days - 1))
        return ((("range", d("l_shipdate"), "<=", cutoff),),)

    def q3(rng):
        day = int(rng.integers(YEAR, days - YEAR))
        return ((
            in_atom(d("c_mktsegment"), (int(rng.integers(0, 5)),)),
            ("range", d("o_orderdate"), "<", day),
            ("range", d("l_shipdate"), ">", day),
        ),)

    def q4(rng):
        lo, hi = date_range(rng, 90)
        return ((
            ("range", d("o_orderdate"), ">=", lo),
            ("range", d("o_orderdate"), "<", hi),
            ("adv", d("l_commitdate"), "<", d("l_receiptdate")),
        ),)

    def q5(rng):
        lo, hi = date_range(rng, YEAR)
        return ((
            in_atom(d("r_name"), (int(rng.integers(0, 5)),)),
            ("range", d("o_orderdate"), ">=", lo),
            ("range", d("o_orderdate"), "<", hi),
        ),)

    def q6(rng):
        lo, hi = date_range(rng, YEAR)
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
        lo, hi = date_range(rng, 2 * YEAR)
        return ((
            in_atom(d("c_nationkey"), (a, b)),
            in_atom(d("s_nationkey"), (a, b)),
            ("range", d("l_shipdate"), ">=", lo),
            ("range", d("l_shipdate"), "<", hi),
        ),)

    def q8(rng):
        lo, hi = date_range(rng, 2 * YEAR)
        return ((
            in_atom(d("r_name"), (int(rng.integers(0, 5)),)),
            ("range", d("o_orderdate"), ">=", lo),
            ("range", d("o_orderdate"), "<", hi),
            in_atom(d("p_brand"), rng.choice(25, 3, replace=False)),
        ),)

    def q9(rng):
        return ((
            in_atom(d("s_nationkey"), rng.choice(25, 4, replace=False)),
            in_atom(d("p_container"), rng.choice(40, 8, replace=False)),
        ),)

    def q10(rng):
        lo, hi = date_range(rng, 90)
        return ((
            in_atom(d("l_returnflag"), (2,)),
            ("range", d("o_orderdate"), ">=", lo),
            ("range", d("o_orderdate"), "<", hi),
        ),)

    def q12(rng):
        lo, hi = date_range(rng, YEAR)
        modes = rng.choice(7, size=2, replace=False)
        return ((
            in_atom(d("l_shipmode"), modes),
            ("adv", d("l_commitdate"), "<", d("l_receiptdate")),
            ("adv", d("l_shipdate"), "<", d("l_commitdate")),
            ("range", d("l_receiptdate"), ">=", lo),
            ("range", d("l_receiptdate"), "<", hi),
        ),)

    def q14(rng):
        lo, hi = date_range(rng, 30)
        return ((
            ("range", d("l_shipdate"), ">=", lo),
            ("range", d("l_shipdate"), "<", hi),
        ),)

    def q17(rng):
        return ((
            in_atom(d("p_brand"), (int(rng.integers(0, 25)),)),
            in_atom(d("p_container"), (int(rng.integers(0, 40)),)),
            ("range", d("l_quantity"), "<", int(rng.integers(3, 8))),
        ),)

    def q18(rng):
        return ((("range", d("l_quantity"), ">", int(rng.integers(44, 49))),),)

    def q19(rng):  # OR of three 8-filter conjunctions
        def arm(brands, containers, qlo, size_hi):
            q = int(rng.integers(*qlo))
            return (
                in_atom(d("p_brand"), (int(rng.choice(brands)),)),
                in_atom(d("p_container"), containers),
                ("range", d("l_quantity"), ">=", q),
                ("range", d("l_quantity"), "<=", q + 10),
                ("range", d("p_size"), ">=", 1),
                ("range", d("p_size"), "<=", size_hi),
                in_atom(d("l_shipinstruct"), (0,)),
                in_atom(d("l_shipmode"), (0, 1)),
            )

        return (
            arm(range(0, 8), rng.choice(40, 4, replace=False), (1, 11), 5),
            arm(range(8, 16), rng.choice(40, 4, replace=False), (10, 20), 10),
            arm(range(16, 25), rng.choice(40, 4, replace=False), (20, 30), 15),
        )

    def q21(rng):
        return ((
            ("adv", d("l_receiptdate"), ">", d("l_commitdate")),
            in_atom(d("s_nationkey"), (int(rng.integers(0, 25)),)),
            in_atom(d("l_linestatus"), (0,)),
        ),)

    return {
        "q1": q1, "q3": q3, "q4": q4, "q5": q5, "q6": q6, "q7": q7,
        "q8": q8, "q9": q9, "q10": q10, "q12": q12, "q14": q14,
        "q17": q17, "q18": q18, "q19": q19, "q21": q21,
    }


def queries(schema: Schema, n: int, seed: int) -> list:
    """``n / 15`` instances of every template, template by template."""
    per, rest = divmod(n, MULTIPLE)
    if rest:
        raise ValueError(
            f"tpch query count {n} is not a multiple of {MULTIPLE}")
    rng = np.random.default_rng(seed)
    return [fn(rng) for fn in templates(schema).values() for _ in range(per)]
