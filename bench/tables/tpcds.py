"""TPC-DS ``store_sales`` joined to the dimension attributes its queries
filter on (TPC-DS specification, https://www.tpc.org/tpcds/), denormalized
as the qd-tree paper (arXiv:2004.10898, Sec 7.1) denormalized TPC-H.

At scale factor 1 the fact table has 2,880,404 rows.  Each row draws its
foreign keys, and every dimension attribute is read through its key from
a dimension table at SF1's row count, so the star schema's functional
dependencies hold: 18,000 items, 12 stores, 1,920,800 customer
demographics and 7,200 household demographics (both the specification's
full cross products of their attributes), 50,000 customer addresses and
300 promotions.  The dimension tables are the deployment's, the same for
every seed (``DIM_SEED``), as dsdgen's are for a scale factor; the run's
seed draws the fact rows.

Rows come in tickets of 8 to 16 lines that share the sale's date, time,
store, customer demographics, household and address; tickets run in date
order, so the table streams as daily appends.  Money is in cents.

Codes (every column is an int32 code in ``[0, dom)``):

* ``ss_net_profit`` is cents plus ``PROFIT_OFFSET`` (profit can be
  negative);
* ``d_date`` is the day number from 1998-01-01; ``d_year`` is the year
  less 1998, ``d_moy`` the month less 1, ``d_dom`` the day of the month,
  ``d_dow`` 0 for Sunday, ``d_month_seq`` the specification's month
  sequence less ``MONTH_SEQ0`` (1998-01);
* ``i_manufact_id`` and ``i_manager_id`` are the id less 1;
  ``i_class`` lies in category ``i_class // 10`` and ``i_brand`` in class
  ``i_brand % 100``;
* ``s_store_name`` is the store's number less 1 (dsdgen names a store by
  the syllables of its number, ``'ese'`` for store 4);
* ``hd_vehicle_count`` is the count plus 1 (the specification's counts
  run from -1 to 4); the string attributes are indices into the lists
  below.

A column the templates of ``families/tpcds.py`` test only by ``=`` or
``IN`` is categorical; one they test by ranges is numeric.
"""

from __future__ import annotations

import datetime

import numpy as np

from qdbench.datagen import Col, Schema

DIM_SEED = 20_000_101  # the SF1 dimension tables
FIRST_DAY = datetime.date(1998, 1, 1)
DAYS = 1826  # 1998-01-01 .. 2002-12-31
MONTH_SEQ0 = 1176  # d_month_seq of 1998-01
PROFIT_OFFSET = 1_000_000  # cents: the least profit is -100 x 100.00

N_ITEM, N_STORE, N_HD, N_ADDR, N_PROMO = 18_000, 12, 7_200, 50_000, 300
N_CD = 1_920_800
CATEGORIES = ("Women", "Men", "Children", "Shoes", "Music", "Jewelry",
              "Home", "Sports", "Books", "Electronics")
N_CLASS, N_BRAND = 100, 712
GENDER = ("M", "F")
MARITAL = ("M", "S", "D", "W", "U")
EDUCATION = ("Primary", "Secondary", "College", "2 yr Degree",
             "4 yr Degree", "Advanced Degree", "Unknown")
BUY_POTENTIAL = (">10000", "5001-10000", "1001-5000", "501-1000", "0-500",
                 "Unknown")
N_STATE = 51  # the 50 states and DC
N_COUNTY = N_CITY = 30
GMT = (-5, -6)  # the offsets q43's GMT substitution draws
YN = ("N", "Y")


def schema() -> Schema:
    N, C = "numeric", "categorical"
    return Schema((
        Col("ss_quantity", N, 101),
        Col("ss_list_price", N, 30_001),
        Col("ss_sales_price", N, 30_001),
        Col("ss_coupon_amt", N, 3_000_001),
        Col("ss_wholesale_cost", N, 10_001),
        Col("ss_net_profit", N, 4_000_001),
        Col("d_date", N, DAYS),
        Col("d_year", C, 5),
        Col("d_moy", C, 12),
        Col("d_dom", N, 32),
        Col("d_dow", C, 7),
        Col("d_month_seq", C, 60),
        Col("t_hour", C, 24),
        Col("t_minute", N, 60),
        Col("i_category", C, len(CATEGORIES)),
        Col("i_class", C, N_CLASS),
        Col("i_brand", C, N_BRAND),
        Col("i_manufact_id", C, 1000),
        Col("i_manager_id", C, 100),
        Col("s_store_name", C, N_STORE),
        Col("s_state", C, N_STATE),
        Col("s_county", C, N_COUNTY),
        Col("s_city", C, N_CITY),
        Col("s_gmt_offset", C, len(GMT)),
        Col("s_number_employees", N, 301),
        Col("cd_gender", C, len(GENDER)),
        Col("cd_marital_status", C, len(MARITAL)),
        Col("cd_education_status", C, len(EDUCATION)),
        Col("hd_buy_potential", C, len(BUY_POTENTIAL)),
        Col("hd_dep_count", C, 10),
        Col("hd_vehicle_count", N, 6),
        Col("ca_state", C, N_STATE),
        Col("ca_country", C, 1),
        Col("p_channel_email", C, len(YN)),
        Col("p_channel_event", C, len(YN)),
    ))


def date_dim() -> dict:
    """The date attributes of every day of the sales span, by day number."""
    days = [FIRST_DAY + datetime.timedelta(k) for k in range(DAYS)]
    return {
        "d_year": np.array([d.year - 1998 for d in days]),
        "d_moy": np.array([d.month - 1 for d in days]),
        "d_dom": np.array([d.day for d in days]),
        "d_dow": np.array([(d.weekday() + 1) % 7 for d in days]),
        "d_month_seq": np.array([(d.year - 1998) * 12 + d.month - 1
                                 for d in days]),
    }


def dimensions() -> dict:
    """The SF1 dimension tables: attribute name -> values by key."""
    rng = np.random.default_rng(DIM_SEED)
    brand = rng.integers(0, N_BRAND, N_ITEM)
    cls = brand % N_CLASS
    # each state lies in one of q43's two time zones (the parity of its
    # code), so a store's offset follows from its state
    state = rng.integers(0, N_STATE, N_STORE)
    hd = np.arange(N_HD)
    return {
        "i_category": cls // 10,
        "i_class": cls,
        "i_brand": brand,
        "i_manufact_id": rng.integers(0, 1000, N_ITEM),
        "i_manager_id": rng.integers(0, 100, N_ITEM),
        "s_store_name": np.arange(N_STORE),
        "s_state": state,
        "s_county": rng.integers(0, N_COUNTY, N_STORE),
        "s_city": rng.integers(0, N_CITY, N_STORE),
        "s_gmt_offset": state % len(GMT),
        "s_number_employees": rng.integers(200, 301, N_STORE),
        # household demographics: the cross product of 20 income bands,
        # 6 buy potentials, 10 dependent counts and 6 vehicle counts
        "hd_vehicle_count": hd % 6,
        "hd_dep_count": hd // 6 % 10,
        "hd_buy_potential": hd // 60 % 6,
        "ca_state": rng.integers(0, N_STATE, N_ADDR),
        "ca_country": np.zeros(N_ADDR, np.int64),
        "p_channel_email": rng.integers(0, 2, N_PROMO),
        "p_channel_event": rng.integers(0, 2, N_PROMO),
    }


KEY_OF = {  # dimension attribute -> the fact's key it is read through
    **{a: "item" for a in ("i_category", "i_class", "i_brand",
                           "i_manufact_id", "i_manager_id")},
    **{a: "store" for a in ("s_store_name", "s_state", "s_county", "s_city",
                            "s_gmt_offset", "s_number_employees")},
    **{a: "hdemo" for a in ("hd_buy_potential", "hd_dep_count",
                            "hd_vehicle_count")},
    **{a: "addr" for a in ("ca_state", "ca_country")},
    **{a: "promo" for a in ("p_channel_email", "p_channel_event")},
}


def star(n: int, seed: int) -> tuple[dict, np.ndarray]:
    """``n`` fact rows from ``seed``: their foreign keys (``date``,
    ``item``, ``store``, ``cdemo``, ``hdemo``, ``addr``, ``promo``) and
    the denormalized table."""
    rng = np.random.default_rng(seed)
    lines = rng.integers(8, 17, n // 8 + 1)
    n_tickets = int(np.searchsorted(np.cumsum(lines), n)) + 1
    ticket = np.repeat(np.arange(n_tickets), lines[:n_tickets])[:n]
    per_ticket = {
        "date": np.sort(rng.integers(0, DAYS, n_tickets)),
        "hour": rng.integers(8, 22, n_tickets),
        "minute": rng.integers(0, 60, n_tickets),
        "store": rng.integers(0, N_STORE, n_tickets),
        "cdemo": rng.integers(0, N_CD, n_tickets),
        "hdemo": rng.integers(0, N_HD, n_tickets),
        "addr": rng.integers(0, N_ADDR, n_tickets),
    }
    keys = {k: v[ticket] for k, v in per_ticket.items()}
    keys["item"] = rng.integers(0, N_ITEM, n)
    keys["promo"] = rng.integers(0, N_PROMO, n)

    # pricing: quantity, wholesale cost, markup, discount and coupon
    qty = rng.integers(1, 101, n)
    wholesale = rng.integers(100, 10_001, n)
    list_price = wholesale * (100 + rng.integers(0, 201, n)) // 100
    sales_price = list_price * (100 - rng.integers(0, 101, n)) // 100
    ext_sales = qty * sales_price
    coupon = np.where(rng.random(n) < 0.2,
                      ext_sales * rng.integers(0, 101, n) // 100, 0)
    profit = ext_sales - coupon - qty * wholesale

    # customer demographics: the cross product of gender (fastest),
    # marital status, education, purchase estimate (20), credit rating
    # (4) and three dependent counts (7 each)
    cd = keys["cdemo"]
    dd, dims = date_dim(), dimensions()
    cols = {
        "ss_quantity": qty,
        "ss_list_price": list_price,
        "ss_sales_price": sales_price,
        "ss_coupon_amt": coupon,
        "ss_wholesale_cost": wholesale,
        "ss_net_profit": profit + PROFIT_OFFSET,
        "d_date": keys["date"],
        **{a: v[keys["date"]] for a, v in dd.items()},
        "t_hour": keys["hour"],
        "t_minute": keys["minute"],
        **{a: dims[a][keys[k]] for a, k in KEY_OF.items()},
        "cd_gender": cd % 2,
        "cd_marital_status": cd // 2 % 5,
        "cd_education_status": cd // 10 % 7,
    }
    sch = schema()
    table = np.empty((n, len(sch)), np.int32)
    for j, c in enumerate(sch):
        table[:, j] = cols[c.name]
    return keys, table


def require_program() -> None:
    """Stop at once where the program cannot build this layout in a
    run's set-up.  At 2,241 categorical bits the greedy builder scores
    its candidate cuts in seconds with the per-column product of
    ``repro.core.query.cat_intersect``; a program that still broadcasts
    (descriptions, conjuncts, domain) per column takes minutes on the
    100,000-row sample, which the run would spend in set-up."""
    from qdbench.system import import_program

    import_program()
    from repro.core import query

    if not hasattr(query, "cat_intersect"):
        raise RuntimeError(
            "tpcds: the program's greedy build has no per-column product "
            "intersect (repro.core.query.cat_intersect); its set-up at "
            f"{schema().cat_bits} categorical bits would take many minutes")


def make(n: int, seed: int) -> np.ndarray:
    require_program()
    return star(n, seed)[1]
