"""The selections of the 22 TPC-DS templates that read ``store_sales``
with its dimensions (TPC-DS specification, https://www.tpc.org/tpcds/,
queries 3, 7, 13, 19, 27, 28, 34, 42, 43, 46, 48, 52, 53, 55, 63, 68, 73,
79, 88, 89, 96 and 98), as atoms over the denormalized table of
``tables/tpcds.py``.

Parameters are drawn as the specification's substitution rules draw them
(a year from 1998..2002, a month from 11..12, a manufacturer from
1..1000, a manager from 1..100, distinct values from a distribution
where it asks for a unique list), over the table's codes.  A
disjunction is kept as conjuncts of the query's DNF: 7's two promotion
channels, 13's and 48's three demographic arms by three address arms
(9 conjuncts), 28's six quantity buckets by three price ranges (18),
53's and 63's two item arms, and 88's eight half hours by three
household arms; 34, 46, 68, 79 and 89 keep theirs too.

Departures from the specification:

* joins and aggregates are out of scope: only the selection decides
  which blocks a query skips;
* the ratio ``hd_dep_count / hd_vehicle_count > 1.2`` of 34 and 73 and
  the zip comparison ``substr(ca_zip,1,5) <> substr(s_zip,1,5)`` of 19
  are dropped (neither is a cut over one column);
* 88's household arm for a dependent count of -1 (its substitution
  draws from -1..4) selects no row and is left out;
* the literal categories, classes and brands of 53 and 63 are fixed
  codes of the table's item hierarchy in the same relation: classes of
  the arm's categories and brands of those classes.
"""

from __future__ import annotations

import numpy as np

from qdbench.datagen import Schema, in_atom

MULTIPLE = 22  # build queries come n / 22 to a template
DOLLAR = 100  # money is in cents
PROFIT_OFFSET = 1_000_000  # tables/tpcds.py's code of a zero profit
ESE = 3  # s_store_name code of 'ese'
JAN1 = (0, 365, 730, 1096, 1461)  # d_date of 01-01, 1998 .. 2002
BUY_POTENTIAL = (">10000", "5001-10000", "1001-5000", "501-1000", "0-500",
                 "Unknown")
# 53 and 63: (categories, classes, brands) of each arm
ITEM_ARMS = (
    ((8, 2, 9), (92, 95, 83, 87), (192, 295, 83, 387)),
    ((0, 4, 1), (3, 41, 6, 17), (103, 241, 406, 517)),
)


def templates(schema: Schema) -> dict:
    """Name -> ``fn(rng) -> query``."""
    d = schema.dim
    dom = {c.name: c.dom for c in schema}

    def eq(col, code):
        return in_atom(d(col), (code,))

    def between(col, lo, hi):
        return (("range", d(col), ">=", int(lo)),
                ("range", d(col), "<=", int(hi)))

    def pick(rng, col, k):
        return [int(v) for v in rng.choice(dom[col], k, replace=False)]

    def year(rng, last=2002):
        return int(rng.integers(1998, last + 1)) - 1998

    def years3(rng):
        y = year(rng, 2000)
        return in_atom(d("d_year"), (y, y + 1, y + 2))

    def month(rng):
        return int(rng.integers(11, 13)) - 1

    def money(col, lo, hi):
        return between(col, lo * DOLLAR, hi * DOLLAR)

    def profit(lo, hi):
        return between("ss_net_profit", PROFIT_OFFSET + lo * DOLLAR,
                       PROFIT_OFFSET + hi * DOLLAR)

    def vehicles(op, count):
        """A comparison with a vehicle count (coded count + 1), its
        literal held inside the domain."""
        return ("range", d("hd_vehicle_count"), op,
                min(count + 1, dom["hd_vehicle_count"] - 1))

    def demographics(rng):
        return (eq("cd_gender", int(rng.integers(0, 2))),
                eq("cd_marital_status", int(rng.integers(0, 5))),
                eq("cd_education_status", int(rng.integers(0, 7))))

    def manager_month(rng, manager):
        return ((eq("d_moy", month(rng)), eq("i_manager_id", manager),
                 eq("d_year", year(rng))),)

    def buy_potential(rng):
        one = rng.choice([">10000", "501-1000", "1001-5000"])
        two = rng.choice(["Unknown", "5001-10000", "0-500"])
        return in_atom(d("hd_buy_potential"),
                       (BUY_POTENTIAL.index(one), BUY_POTENTIAL.index(two)))

    def demo_by_address(rng, base, prices, deps):
        """13's and 48's DNF: three demographic arms by three address
        arms."""
        ms, es, states = (pick(rng, "cd_marital_status", 3),
                          pick(rng, "cd_education_status", 3),
                          pick(rng, "ca_state", 9))
        arms_a = [(eq("cd_marital_status", ms[k]),
                   eq("cd_education_status", es[k]),
                   *money("ss_sales_price", *prices[k]))
                  + ((eq("hd_dep_count", deps[k]),) if deps else ())
                  for k in range(3)]
        profits = ((100, 200), (150, 300), (50, 250)) if deps else (
            (0, 2000), (150, 3000), (50, 25000))
        arms_b = [(eq("ca_country", 0),
                   in_atom(d("ca_state"), states[3 * k:3 * k + 3]),
                   *profit(*profits[k])) for k in range(3)]
        return tuple(base + a + b for a in arms_a for b in arms_b)

    def q3(rng):
        return ((eq("d_moy", month(rng)),
                 eq("i_manufact_id", int(rng.integers(0, 1000)))),)

    def q7(rng):
        base = demographics(rng) + (eq("d_year", year(rng)),)
        return (base + (eq("p_channel_email", 0),),
                base + (eq("p_channel_event", 0),))

    def q13(rng):
        return demo_by_address(rng, (eq("d_year", 2001 - 1998),),
                               ((100, 150), (50, 100), (150, 200)),
                               (3, 1, 1))

    def q19(rng):
        return ((eq("i_manager_id", int(rng.integers(0, 100))),
                 eq("d_moy", month(rng)), eq("d_year", year(rng))),)

    def q27(rng):
        return (demographics(rng) + (
            eq("d_year", year(rng)),
            in_atom(d("s_state"), pick(rng, "s_state", 6))),)

    def q28(rng):
        out = []
        for k in range(6):
            qty = between("ss_quantity", 0 if k == 0 else 5 * k + 1,
                          5 * k + 5)
            lp = int(rng.integers(0, 191))
            ca = int(rng.integers(0, 18001))
            wc = int(rng.integers(0, 81))
            out += [qty + money("ss_list_price", lp, lp + 10),
                    qty + money("ss_coupon_amt", ca, ca + 1000),
                    qty + money("ss_wholesale_cost", wc, wc + 20)]
        return tuple(out)

    def q34(rng):
        base = (buy_potential(rng), vehicles(">", 0), years3(rng),
                in_atom(d("s_county"), pick(rng, "s_county", 8)))
        return (between("d_dom", 1, 3) + base,
                between("d_dom", 25, 28) + base)

    def q42(rng):
        return manager_month(rng, 0)

    def q43(rng):
        return ((eq("d_year", year(rng)),
                 eq("s_gmt_offset", int(rng.integers(0, 2)))),)

    def household_or(rng, vehicle_op):
        dep, veh = int(rng.integers(0, 10)), int(rng.integers(-1, 5))
        arm_v = ((vehicles(">=", veh), vehicles("<=", veh))
                 if vehicle_op == "=" else (vehicles(">", veh),))
        return ((eq("hd_dep_count", dep),), arm_v)

    def q46(rng):
        base = (in_atom(d("d_dow"), (6, 0)), years3(rng),
                in_atom(d("s_city"), pick(rng, "s_city", 5)))
        return tuple(base + arm for arm in household_or(rng, "="))

    def q48(rng):
        return demo_by_address(rng, (eq("d_year", year(rng)),),
                               ((100, 150), (50, 100), (150, 200)), ())

    def q52(rng):
        return manager_month(rng, 0)

    def item_arms(rng):
        seq = int(rng.integers(1176, 1225)) - 1176
        months = in_atom(d("d_month_seq"), range(seq, seq + 12))
        return tuple((months, in_atom(d("i_category"), cats),
                      in_atom(d("i_class"), classes),
                      in_atom(d("i_brand"), brands))
                     for cats, classes, brands in ITEM_ARMS)

    def q53(rng):
        return item_arms(rng)

    def q55(rng):
        return manager_month(rng, int(rng.integers(0, 100)))

    def q63(rng):
        return item_arms(rng)

    def q68(rng):
        base = between("d_dom", 1, 2) + (
            years3(rng), in_atom(d("s_city"), pick(rng, "s_city", 2)))
        return tuple(base + arm for arm in household_or(rng, "="))

    def q73(rng):
        return (between("d_dom", 1, 2) + (
            buy_potential(rng), vehicles(">", 0), years3(rng),
            in_atom(d("s_county"), pick(rng, "s_county", 4))),)

    def q79(rng):
        base = (eq("d_dow", 1), years3(rng),
                *between("s_number_employees", 200, 295))
        return tuple(base + arm for arm in household_or(rng, ">"))

    def q88(rng):
        deps = [int(v) for v in rng.choice(np.arange(-1, 5), 3,
                                           replace=False)]
        out = []
        for k in range(8):  # 8:30-9:00, 9:00-9:30, ..., 12:00-12:30
            hour, late = 8 + (k + 1) // 2, k % 2 == 0
            when = (eq("t_hour", hour),
                    ("range", d("t_minute"), ">=" if late else "<", 30),
                    eq("s_store_name", ESE))
            out += [when + (eq("hd_dep_count", dep), vehicles("<=", dep + 2))
                    for dep in deps if dep >= 0]
        return tuple(out)

    def q89(rng):
        cats = pick(rng, "i_category", 6)
        classes = [10 * c + int(rng.integers(0, 10)) for c in cats]
        y = in_atom(d("d_year"), (year(rng),))
        return tuple((y, in_atom(d("i_category"), cats[k:k + 3]),
                      in_atom(d("i_class"), classes[k:k + 3]))
                     for k in (0, 3))

    def q96(rng):
        return ((eq("t_hour", int(rng.choice([20, 15, 16, 8]))),
                 ("range", d("t_minute"), ">=", 30),
                 eq("hd_dep_count", int(rng.integers(0, 10))),
                 eq("s_store_name", ESE)),)

    def q98(rng):  # 30 days from a date in 01-01 .. 07-24 of the year
        start = JAN1[year(rng)] + int(rng.integers(0, 205))
        return ((in_atom(d("i_category"), pick(rng, "i_category", 3)),
                 *between("d_date", start, start + 30)),)

    return {
        "q3": q3, "q7": q7, "q13": q13, "q19": q19, "q27": q27, "q28": q28,
        "q34": q34, "q42": q42, "q43": q43, "q46": q46, "q48": q48,
        "q52": q52, "q53": q53, "q55": q55, "q63": q63, "q68": q68,
        "q73": q73, "q79": q79, "q88": q88, "q89": q89, "q96": q96,
        "q98": q98,
    }


def queries(schema: Schema, n: int, seed: int) -> list:
    """``n / 22`` instances of every template, template by template."""
    per, rest = divmod(n, MULTIPLE)
    if rest:
        raise ValueError(
            f"tpcds query count {n} is not a multiple of {MULTIPLE}")
    rng = np.random.default_rng(seed)
    return [fn(rng) for fn in templates(schema).values() for _ in range(per)]
