"""TPC-H lineitem, denormalized to the 21 columns the templates of
``families/tpch.py`` touch (the qd-tree paper, arXiv:2004.10898, Sec 7.1):
the same column families, domains and date correlations as the
repository's TPC-H-like generator, in plain numpy."""

from __future__ import annotations

import numpy as np

from qdbench.datagen import Col, Schema

DATE_DOM = 2526  # days in TPC-H's 1992-01-01 .. 1998-12-01 span


def schema() -> Schema:
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


def make(n: int, seed: int) -> np.ndarray:
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
