"""CPU tests of the TPC-DS ``store_sales`` deployment under ``bench/``:
its table keeps the star schema's functional dependencies, its family
keeps the 22 templates and their disjunctions, both stay as they were
generated, its bulk-load calls fill 128 MiB, every configuration's
greedy layout stays the tree it was, and the build's spans are read.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from qdbench import cells, datagen, spec as speclib, traffic as tr  # noqa: E402

SPEC = speclib.Spec()
TABLE = SPEC.load("tables", "tpcds")
CFG = SPEC.config("tpcds_sf1")


def test_tpcds_dimension_attributes_follow_their_keys():
    """Every item, store, household, address, promotion and demographic
    attribute is a function of the fact row's key, as in the star the
    table denormalizes; the item hierarchy nests brand in class in
    category."""
    keys, t = TABLE.star(40_000, 2**31 + 3)
    schema = TABLE.schema()
    for attr, key in TABLE.KEY_OF.items():
        col = t[:, schema.dim(attr)]
        k = keys[key]
        order = np.argsort(k, kind="stable")
        k, col = k[order], col[order]
        same_key = k[1:] == k[:-1]
        assert same_key.any(), attr
        assert (col[1:][same_key] == col[:-1][same_key]).all(), attr
    d = schema.dim
    for attr, radix in (("cd_gender", 1), ("cd_marital_status", 2),
                        ("cd_education_status", 10)):
        dom = schema[d(attr)].dom
        assert (t[:, d(attr)] == keys["cdemo"] // radix % dom).all()
    assert (t[:, d("i_class")] // 10 == t[:, d("i_category")]).all()
    assert (t[:, d("i_brand")] % 100 == t[:, d("i_class")]).all()
    dates = TABLE.date_dim()
    for attr, by_day in dates.items():
        assert (t[:, d(attr)] == by_day[keys["date"]]).all()
    assert (np.diff(keys["date"]) >= 0).all()  # tickets in date order
    assert len(np.unique(keys["item"])) > 15_000
    # the dimension tables are the deployment's, whatever the run's seed
    dims = TABLE.dimensions()
    for seed in (2**31 + 3, 11):
        keys, t = TABLE.star(5000, seed)
        for attr, key in TABLE.KEY_OF.items():
            assert (t[:, d(attr)] == dims[attr][keys[key]]).all(), attr


def test_tpcds_family_keeps_its_templates_and_disjunctions():
    schema = TABLE.schema()
    templates = datagen.templates(SPEC, "tpcds", schema)
    fam = SPEC.load("families", "tpcds")
    assert len(templates) == fam.MULTIPLE == 22
    assert sorted(int(n[1:]) for n in templates) == [
        3, 7, 13, 19, 27, 28, 34, 42, 43, 46, 48, 52, 53, 55, 63, 68, 73, 79,
        88, 89, 96, 98]
    rng = np.random.default_rng(0)
    q13, q28, q48 = (templates[n](rng) for n in ("q13", "q28", "q48"))
    # 3 demographic arms x 3 address arms
    assert len(q13) == len(q48) == 9
    assert all(len(c) == 10 for c in q13) and all(len(c) == 9 for c in q48)
    # 6 quantity buckets x (list price | coupon | wholesale cost)
    assert len(q28) == 18 and all(len(c) == 4 for c in q28)
    price_cols = [{a[1] for a in c[2:]} for c in q28]
    assert len({next(iter(p)) for p in price_cols}) == 3
    assert all(len(templates["q7"](rng)) == 2 for _ in range(3))
    assert CFG["build_queries"] == 5 * fam.MULTIPLE


GOLDEN = {  # SHA-256 of the TPC-DS generators' output
    "table": "b8c3fa44278c9ec91a586776d4eea622a94f4472e68a88e6ed6cb19d22b589ca",
    "queries": "b094734e47bb48ba14e14696e63bdc200d49aeb3dc90a62a8a05b2adfb50c0fd",
}


def test_tpcds_generators_match_their_golden_digests():
    schema, table = datagen.make_table(SPEC, "tpcds", 5000, 123)
    build = datagen.make_queries(SPEC, "tpcds", schema, 110,
                                 CFG["layout_seed"])
    got = {"table": table.tobytes(), "queries": repr(build).encode()}
    assert {k: hashlib.sha256(v).hexdigest() for k, v in got.items()} == GOLDEN


def test_tpcds_bulk_load_calls_are_14_batches_of_35_columns():
    per = tr.rows_per_call(SPEC.traffic("bulk_load"), CFG, CFG["columns"])
    assert per == 14 * 65536 == 917_504
    assert CFG["columns"] == len(TABLE.schema()) == 35
    assert TABLE.schema().cat_bits == 2241


# (blocks, depth, fingerprint) of each configuration's layout
LAYOUTS = {"tpch_sf1": (731, 21, "311f4d22b6f4"),
           "tpcds_sf1": (721, 20, "ec847cf988be")}


@pytest.mark.parametrize("config", sorted(LAYOUTS))
def test_each_configuration_keeps_its_layout(config):
    """The layout every run drives, learned at full size on the
    configuration's 100,000-row sample (the table itself cut short)."""
    from qdbench.system import Program

    cfg = SPEC.config(config) | {"rows": 1000}
    lay = cells.set_up_layout(SPEC, cfg, 5, Program(), lambda m: None)
    got = (lay["layout"]["n_leaves"], lay["layout"]["depth"],
           cells.fingerprint(lay["layout"]))
    assert got == LAYOUTS[config] and lay["layout_faults"] == 0


def test_build_spans_counts_the_builders_scoring():
    from qdbench.system import Program

    spec = importlib.util.spec_from_file_location("qdbench_build_spans",
                                                  BENCH / "build_spans.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = CFG | {"rows": 2000, "sample_rows": 3000, "min_block": 60}
    line = mod.measure(SPEC, cfg, 7, Program(), lambda m: None)
    score = line["build_spans"]["build.score"]
    internal = line["blocks"] - 1
    assert internal > 0 and internal <= score["count"] <= 2 * line["blocks"]
    assert 0 < score["total_s"] <= line["phases"]["build"]
    assert json.loads(json.dumps(line)) == line


def test_tpcds_table_stops_at_once_without_the_product_intersect(
        monkeypatch):
    """A program whose greedy build still broadcasts per column would
    spend many minutes in set-up at this width: the run stops first."""
    from qdbench.system import import_program

    import_program()
    from repro.core import query

    assert TABLE.make(10, 1).shape == (10, 35)
    monkeypatch.delattr(query, "cat_intersect")
    with pytest.raises(RuntimeError, match="cat_intersect"):
        TABLE.make(10, 1)
