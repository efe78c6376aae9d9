"""CPU tests of the benchmark harness under ``bench/``.

They check what the harness decides on its own (discovery by name, the
generators, the plain reference, the schedule, the trace reduction, the
device check) and drive whole runs of each kind of cell at a tiny size,
with the program, with the control in its place, and with faults planted
in the program underneath: ``correct`` must hold for the first and fail
for the others.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import pathlib
import re
import shutil
import sys
import textwrap
import threading
import time
import types
from collections import Counter

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from qdbench import (  # noqa: E402
    cells, datagen, reference, spec as speclib, trace, traffic as tr, work,
)

SPEC = speclib.Spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _found(kind: str, suffix: str = "py") -> list:
    """Names of the files of one kind under ``bench/``: tables, families,
    configs."""
    return sorted(p.stem for p in (BENCH / kind).glob(f"*.{suffix}"))


def _tpch_schema():
    return SPEC.load("tables", "tpch").schema()


# family -> the table of a configuration that names it
TABLE_OF = {c["query_family"]: c["table"] for c in
            (_config(name) for name in _found("configs", "json"))}


# ---------------------------------------------------------------------------
# BENCHMARK.json and discovery by name
# ---------------------------------------------------------------------------
def test_benchmark_json_keeps_its_contract():
    data = SPEC.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert data["paths"] == ["bench"]
    assert data["command"][1] == "bench/run.py"
    names = [m["name"] for m in data["end_to_end"] + data["per_layer"]]
    names += [c["name"] for c in data["configs"] + data["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in data["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for w in data["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] == 1
        reported = {m["name"] for m in SPEC.end_to_end(w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        assert SPEC.per_layer(w["name"])
    for m in data["per_layer"]:
        for cell in m["workloads"]:
            assert m["moves"] in {x["name"] for x in SPEC.end_to_end(cell)}


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC.data["workloads"]])
def test_every_cell_finds_its_files_by_name(cell):
    w = SPEC.cell(cell)
    cfg = SPEC.config(w["config"])
    mix = SPEC.traffic(w["traffic"])
    assert cfg["name"] == w["config"]
    assert mix["kind"] in cells.KINDS
    assert len(SPEC.load("tables", cfg["table"]).schema()) == cfg["columns"]
    family = SPEC.load("families", cfg["query_family"])
    assert cfg["build_queries"] % family.MULTIPLE == 0
    if mix["kind"] == "open_loop":
        assert callable(SPEC.load("requests", mix["requests"]).requests)
    for m in SPEC.per_layer(cell):
        assert callable(SPEC.reader(m["name"]))


def test_a_new_metric_mix_and_config_are_found_without_editing(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    data = json.loads(json.dumps(SPEC.data))
    (tmp_path / "bench" / "metrics" / "serve.new_count.py").write_text(
        "def read(layer, peaks):\n    return 7.0\n")
    (tmp_path / "bench" / "traffic" / "new_mix.json").write_text(
        json.dumps({"kind": "bulk_load", "call_bytes": 1 << 20,
                    "check_calls": 1}))
    cfg = SPEC.config("tpch_sf1") | {"name": "new_config"}
    (tmp_path / "bench" / "configs" / "new_config.json").write_text(
        json.dumps(cfg))
    data["configs"].append({"name": "new_config", "source": "x",
                            "file": "bench/configs/new_config.json",
                            "reduced": [], "why": "x"})
    data["workloads"].append({"name": "new_config.load", "config": "new_config",
                              "traffic": "new_mix", "chips": 1, "why": "x"})
    data["end_to_end"][0]["workloads"].append("new_config.load")
    data["per_layer"].append({"name": "serve.new_count", "unit": "count",
                              "better": "lower", "source": "program_counter",
                              "layer": "serving front end",
                              "moves": "ingest_rows_per_s",
                              "workloads": ["new_config.load"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    spec = speclib.Spec(tmp_path)
    assert spec.config("new_config")["name"] == "new_config"
    assert spec.traffic("new_mix")["call_bytes"] == 1 << 20
    found = {m["name"] for m in spec.per_layer("new_config.load")}
    assert found == {"serve.new_count"}
    assert spec.reader("serve.new_count")({}, {}) == 7.0
    assert "serve.new_count" not in {
        m["name"] for m in spec.per_layer("tpch_sf1.ingest")}


TOY_TABLE = """
import numpy as np
from qdbench.datagen import Col, Schema

def schema():
    return Schema((Col("x0", "numeric", 100), Col("x1", "categorical", 6),
                   Col("x2", "numeric", 50)))

def make(n, seed):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, 100, n), rng.zipf(1.5, n) % 6,
                     rng.integers(0, 50, n)], axis=1).astype(np.int32)
"""
TOY_FAMILY = """
import numpy as np
from qdbench.datagen import in_atom

MULTIPLE = 1

def templates(schema):
    def t(rng):
        return ((("range", 0, "<", int(rng.integers(1, 100))),
                 in_atom(1, rng.choice(6, 2, replace=False))),)
    return {"t": t}

def queries(schema, n, seed):
    rng = np.random.default_rng(seed)
    return [templates(schema)["t"](rng) for _ in range(n)]
"""
TOY_DRAW = """
import numpy as np
from qdbench import datagen

def requests(spec, traffic, cfg, schema, n, seed):
    build = datagen.make_queries(spec, cfg["query_family"], schema,
                                 cfg["build_queries"], cfg["layout_seed"])
    return [build[i] for i in
            np.random.default_rng(seed).integers(0, len(build), n)]
"""
def _digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_table_family_and_request_draw_run_without_editing(tmp_path):
    """A new deployment is new files and new entries: a copy of ``bench/``
    given a table, a query family and a request draw of its own runs a
    bulk-load and an open-loop cell of them, correct, with every file it
    had left as it was."""
    from qdbench.system import Program

    ignore = shutil.ignore_patterns("__pycache__", "tests")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=ignore)
    before = _digests(tmp_path / "bench")
    assert before == {k: v for k, v in _digests(BENCH).items()
                      if not k.startswith("tests/")}
    new = {"tables/toy.py": TOY_TABLE, "families/toy.py": TOY_FAMILY,
           "requests/toy_repeat.py": TOY_DRAW,
           "configs/toy.json": json.dumps({
               "name": "toy", "table": "toy", "rows": 12000, "columns": 3,
               "query_family": "toy", "build_queries": 12,
               "strategy": "greedy", "sample_rows": 600, "layout_seed": 0,
               "min_block": 40, "max_adv": 1, "batch": 2048}),
           "traffic/toy_load.json": json.dumps({
               "kind": "bulk_load", "call_bytes": 2048 * 4 * 3 * 2,
               "check_calls": 2}),
           "traffic/toy_open.json": json.dumps({
               "kind": "open_loop", "requests": "toy_repeat", "rate_qps": 100,
               "warm_seconds": 0.3, "check_requests": 0,
               "filter_requests": 4})}
    for rel, text in new.items():
        (tmp_path / "bench" / rel).write_text(textwrap.dedent(text))
    data = json.loads(json.dumps(SPEC.data))
    data["configs"].append({"name": "toy", "source": "x", "reduced": [],
                            "file": "bench/configs/toy.json", "why": "x"})
    data["workloads"] += [
        {"name": "toy.load", "config": "toy", "traffic": "toy_load",
         "chips": 1, "why": "x"},
        {"name": "toy.serve", "config": "toy", "traffic": "toy_open",
         "chips": 1, "why": "x"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    spec = speclib.Spec(tmp_path)
    for cell in ("toy.load", "toy.serve"):
        w = spec.cell(cell)
        out = cells.run(spec, spec.config(w["config"]),
                        spec.traffic(w["traffic"]), 2**31 + 5, 0.4, False,
                        Program(), lambda m: None)
        assert out.correct and out.failed == 0 and out.attempted > 0, \
            (cell, out.checks)
    after = _digests(tmp_path / "bench")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == set(new)


def test_a_missing_file_or_template_is_named():
    with pytest.raises(FileNotFoundError, match=r"tables/absent\.py"):
        datagen.make_table(SPEC, "absent", 10, 0)
    with pytest.raises(FileNotFoundError, match=r"requests/absent\.py"):
        tr.requests(SPEC, {"requests": "absent"}, {}, _tpch_schema(), 10, 0)
    bare = types.SimpleNamespace(__file__="families/bare.py",
                                 queries=lambda schema, n, seed: [])
    only_bare = types.SimpleNamespace(load=lambda kind, name: bare)
    with pytest.raises(ValueError, match="'bare' has no templates"):
        datagen.templates(only_bare, "bare", _tpch_schema())


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------
GOLDEN = {  # SHA-256 of the TPC-H generators' output before they became files
    "table": "22635f4a3f8babc5f5c1febff31c557b7e4e53845794171b2296b87e81257c73",
    "queries": "cad958b80debdf5040963b3fa1c6973706ec992ba3c65cd15254eb55f8439462",
    "requests": "645c3c8541cda8349774c0b8b7a9f92f7073abb3b7350b95c96a6cf8438da7f6",
}


def test_tpch_generators_match_their_golden_digests():
    schema, table = datagen.make_table(SPEC, "tpch", 5000, 123)
    cfg = _config("tpch_sf1")
    build = datagen.make_queries(SPEC, "tpch", schema, 75, cfg["layout_seed"])
    plain = tr.requests(SPEC, SPEC.traffic("tpch_adhoc_fresh"), cfg, schema,
                        300, 7)
    got = {"table": table.tobytes(), "queries": repr(build).encode(),
           "requests": repr(plain).encode()}
    assert {k: hashlib.sha256(v).hexdigest() for k, v in got.items()} == GOLDEN


# (name, width): every table under bench/tables/, its width from its schema
TABLES = [(n, len(SPEC.load("tables", n).schema())) for n in _found("tables")]
# (name, n): every family under bench/families/, n a multiple it gives
FAMILIES = [(n, 5 * SPEC.load("families", n).MULTIPLE)
            for n in _found("families")]


@pytest.mark.parametrize("table,width", TABLES)
def test_tables_are_seeded_and_keep_their_widths_and_domains(table, width):
    schema, a = datagen.make_table(SPEC, table, 5000, 123)
    _, b = datagen.make_table(SPEC, table, 5000, 123)
    _, c = datagen.make_table(SPEC, table, 5000, 124)
    assert a.dtype == np.int32 and a.shape == (5000, width) == (5000, len(schema))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert (a >= 0).all() and (a < schema.doms[None, :]).all()
    # every categorical code of a small domain occurs
    for d in np.nonzero(schema.is_cat)[0]:
        if schema[d].dom <= 8:
            assert len(np.unique(a[:, d])) == schema[d].dom


def test_tpch_table_keeps_its_date_correlations():
    schema, t = datagen.make_table(SPEC, "tpch", 20000, 5)
    d = schema.dim
    ship, commit, receipt = (t[:, d(n)] for n in
                             ("l_shipdate", "l_commitdate", "l_receiptdate"))
    assert (receipt > ship).mean() > 0.99
    assert 0.2 < (commit < ship).mean() < 0.5
    assert (t[:, d("o_orderdate")] <= ship).all()


@pytest.mark.parametrize("family,n", FAMILIES)
def test_queries_are_seeded_and_in_domain(family, n):
    assert family in TABLE_OF, f"no configuration names family {family!r}"
    schema = SPEC.load("tables", TABLE_OF[family]).schema()
    qs = datagen.make_queries(SPEC, family, schema, n, 9)
    assert qs == datagen.make_queries(SPEC, family, schema, n, 9)
    assert qs != datagen.make_queries(SPEC, family, schema, n, 10)
    assert len(qs) == n
    for q in qs:
        for conj in q:
            for atom in conj:
                if atom[0] == "in":
                    assert schema[atom[1]].kind == "categorical"
                    assert all(0 <= v < schema[atom[1]].dom for v in atom[2])
                elif atom[0] == "range":
                    assert schema[atom[1]].kind == "numeric"


def test_tpch_family_has_fifteen_templates_and_the_q19_disjunction():
    schema = _tpch_schema()
    templates = datagen.templates(SPEC, "tpch", schema)
    assert len(templates) == SPEC.load("families", "tpch").MULTIPLE == 15
    q19 = templates["q19"](np.random.default_rng(0))
    assert len(q19) == 3 and all(len(c) == 8 for c in q19)


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def test_bulk_load_calls_are_whole_batches_of_128_mib():
    mix = SPEC.traffic("bulk_load")
    assert tr.rows_per_call(mix, _config("tpch_sf1"), 21) == 24 * 65536


def test_ingest_stream_cycles_the_table():
    table = np.arange(70 * 3, dtype=np.int32).reshape(70, 3)
    s = tr.IngestStream(table, 8, 24)
    for call in range(6):
        got = np.concatenate(list(s.batches(call)))
        assert np.array_equal(got, table[s.rows_of(call)])
    assert len(s.pulls) == 18
    assert np.array_equal(np.concatenate(list(s.whole_table())), table)


def test_schedule_gives_every_seed_the_same_gaps():
    a = tr.schedule(500.0, 2.0, 1)
    b = tr.schedule(500.0, 2.0, 2)
    assert len(a) == len(b) == 1000
    assert a[0] == 0.0 and (np.diff(a) > 0).all() and a[-1] < 2.0
    assert not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(np.append(a, 2.0))),
                       np.sort(np.diff(np.append(b, 2.0))))


def test_request_mixes_are_fixed_in_count():
    schema = _tpch_schema()
    cfg = _config("tpch_sf1")
    mix = SPEC.traffic("tpch_adhoc_fresh")
    a, b = (tr.requests(SPEC, mix, cfg, schema, 150, s) for s in (3, 4))
    assert len(a) == len(b) == 150 and len({repr(q) for q in a}) > 100
    assert a == tr.requests(SPEC, mix, cfg, schema, 150, 3) and a != b

    def shapes(qs):  # the template an instance came from, by its atoms
        return Counter(tuple(tuple((x[0], x[1]) for x in c) for c in q)
                       for q in qs)

    assert shapes(a) == shapes(b) and len(shapes(a)) == 15
    assert set(shapes(a).values()) == {10}


# ---------------------------------------------------------------------------
# open-loop timing
# ---------------------------------------------------------------------------
class _Ticket:
    """A request the fake server answers ``delay`` seconds after it was
    submitted, or never; the answer's own ``latency_s`` is wrong."""

    def __init__(self, bids, delay):
        self.answered = threading.Event()
        self.bids = bids
        if delay is not None:
            threading.Timer(delay, self.answered.set).start()

    latency_s = 999.0


class _FakeProgram:
    """Answers ``("ok", id, delay)`` after ``delay`` seconds, holds the
    sender up for ``delay`` on ``("slow", id, delay)`` and then answers
    at once, refuses ``("refuse",)`` at the
    door and never answers a delay of None."""

    @staticmethod
    def query(plain):
        return plain

    @staticmethod
    def submit(server, q):
        if q[0] == "refuse":
            return None
        if q[0] == "slow":
            time.sleep(q[2])
            return _Ticket(np.array([q[1]]), 0.0)
        return _Ticket(np.array([q[1]]), q[2])

    @staticmethod
    def outcome(t, timeout):
        return t.bids if t.answered.wait(timeout) else None


def test_latency_is_timed_from_the_due_time_and_failures_count(monkeypatch):
    monkeypatch.setattr(cells, "LATE_WAIT_S", 0.3)
    due = np.array([0.0, 0.01, 0.02, 0.03, 0.04])
    plain = [("slow", 0, 0.05),  # answered at once, holds the next up
             ("ok", 1, 0.0),  # sent 40 ms after it was due
             ("ok", 2, 0.0),
             ("lose", 3, None),  # admitted, never answered
             ("refuse",)]
    got = cells.open_loop(_FakeProgram(), None, plain, due, cells.Tracer(False))
    lat = got.latency_s
    assert got.refused == 1 and got.lost == 1
    assert [b.tolist() for b in got.lists[:3]] == [[0], [1], [2]]
    assert got.lists[3] is None and got.lists[4] is None
    assert 0.05 <= lat[0] < 0.15
    assert 0.04 <= lat[1] < 0.14 and 0.03 <= lat[2] < 0.13
    assert (got.sent[1] - got.t0 - due[1]) >= 0.039
    assert lat[3] > cells.LATE_WAIT_S and lat[4] > cells.LATE_WAIT_S
    # the tail is over all requests: the two missing ones set it
    assert np.percentile(lat, 95) > cells.LATE_WAIT_S


@pytest.mark.parametrize("delay", [0.0, 0.03])
def test_latency_is_the_clients_own_and_ignores_the_programs(delay):
    due = np.arange(20) * 0.002
    plain = [("ok", i, delay) for i in range(20)]
    got = cells.open_loop(_FakeProgram(), None, plain, due, cells.Tracer(False))
    # every answer's latency_s reads 999 s; the client's clock does not
    assert got.lost == got.refused == 0
    assert (got.latency_s >= delay).all()
    assert (got.latency_s < delay + 0.1).all()


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def test_union_and_gaps_of_busy_intervals():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 6.5], [8, 9]], float)
    u = trace.union(iv)
    assert u.tolist() == [[0, 3], [5, 7], [8, 9]]
    assert trace.gaps(u, 0, 10).tolist() == [[3, 5], [7, 8], [9, 10]]
    assert trace.clip(u, 1, 8.5).tolist() == [[1, 3], [5, 7], [8, 8.5]]


def test_trace_reduction_on_a_synthetic_trace():
    ops = [(100, 300, "%fusion.1 = f32[8] fusion(...)"),
           (250, 400, "%while.3 = (s32[]) while(...)"),
           (600, 700, "%fusion.1 = f32[8] fusion(...)"),
           (950, 1200, "%copy.2 = s32[4] copy(...)")]
    programs = [(90, 450, "jit_step(123)"), (590, 710, "jit_step(123)"),
                (940, 1300, "jit_other(9)")]
    host = [(0, 1000, "bench.ingest_call"), (400, 600, "Transpose"),
            (700, 900, "np.asarray(jax.Array)")]
    s = trace.reduce_events((0, 1000), [(ops, programs)], host)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((300 + 100 + 50) * 1e-9)
    assert s.idle_share == pytest.approx(0.55)
    assert s.n_devices == 1
    assert s.device_ops[0] == ["jit_step %fusion.1", pytest.approx(300e-9)]
    names = dict(s.idle_gaps)
    assert names["Transpose"] == pytest.approx(200e-9)
    assert names["np.asarray(jax.Array)"] == pytest.approx(250e-9)
    assert names["bench.ingest_call"] == pytest.approx(100e-9)


def test_trace_reduction_averages_chips_and_names_unattributed_gaps():
    a = ([(0, 500, "%a = x")], [])
    b = ([(0, 250, "%b = x")], [])
    s = trace.reduce_events((0, 1000), [a, b], [])
    assert s.busy_s == pytest.approx(375e-9)
    assert dict(s.idle_gaps) == {trace.NO_HOST_EVENT: pytest.approx(500e-9)}


# ---------------------------------------------------------------------------
# peaks and work counts
# ---------------------------------------------------------------------------
def test_peaks_table_and_work_counts():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        work.peaks("cpu")
    w = work.ingest_step(65536, 21, 20)
    assert w["bytes"] == 65536 * 21 * 4
    t, bound = work.roofline_s(w, p)
    assert bound == "hbm" and t == pytest.approx(65536 * 84 / 819e9)


def test_roofline_reader_counts_rows_and_width_only():
    read = SPEC.reader("kernel.ingest_step_roofline")
    summary = trace.Summary(window_s=2.0, busy_s=1.0, n_devices=1,
                            device_ops=[], idle_gaps=[])
    layer = {"kind": "ingest", "rows": 819_000_000, "cols": 1, "depth": 1,
             "trace": summary}
    assert read(layer, work.peaks("TPU v5 lite")) == pytest.approx(0.4)
    assert read(layer | {"trace": None}, {}) is None


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _toy_layout():
    # root: x0 < 5 ; left: x1 IN {0} ; right leaf.  leaves 0, 1, 2
    schema = datagen.Schema((datagen.Col("x0", "numeric", 10),
                             datagen.Col("x1", "categorical", 3),
                             datagen.Col("x2", "numeric", 10)))
    mask = np.zeros((3, 3), bool)
    mask[1, 0] = True
    layout = {
        "n_leaves": 3, "depth": 2,
        "node_cut": np.array([0, 1, -1, -1, -1]),
        "node_left": np.array([1, 3, 0, 0, 0]),
        "node_right": np.array([2, 4, 0, 0, 0]),
        "node_leaf": np.array([-1, -1, 2, 0, 1]),
        "cut_kind": np.array(["range", "in", "adv"]),
        "cut_dim": np.array([0, 1, 0]),
        "cut_point": np.array([5, 0, 0]),
        "cut_mask": mask,
        "cut_adv": np.array([-1, -1, 0]),
        "adv": [(0, "<", 2)],
        "cat_offsets": schema.cat_offsets,
    }
    return schema, layout


def test_reference_routes_and_describes_by_brute_force():
    schema, layout = _toy_layout()
    rows = np.array([[1, 0, 3], [4, 2, 1], [7, 1, 9], [9, 0, 2], [2, 0, 0]],
                    np.int32)
    cols = reference.columns(rows)
    leaf = reference.route(layout, cols)
    assert leaf.tolist() == [0, 1, 2, 2, 0]
    d = reference.describe(layout, schema, cols, leaf)
    assert d["counts"].tolist() == [2, 1, 2]
    assert d["lo"][0].tolist() == [1, 0, 0] and d["hi"][0].tolist() == [3, 1, 4]
    assert d["cat"][2].tolist() == [True, True, False]
    assert d["adv"][0].tolist() == [[True, True]]  # 1 < 3 holds, 2 < 0 fails
    assert d["adv"][1].tolist() == [[False, True]]
    empty = reference.describe(layout, schema, cols[:, :1], leaf[:1])
    assert empty["counts"].tolist() == [1, 0, 0]
    assert not empty["lo"][1:].any() and not empty["cat"][1:].any()


def test_reference_query_blocks_and_full_table_filter():
    schema, layout = _toy_layout()
    rows = np.array([[1, 0, 3], [4, 2, 1], [7, 1, 9], [9, 0, 2], [2, 0, 0]],
                    np.int32)
    cols = reference.columns(rows)
    leaf = reference.route(layout, cols)
    d = reference.describe(layout, schema, cols, leaf)
    q_range = ((("range", 0, ">=", 8),),)
    assert reference.query_blocks(d, schema, layout, q_range).tolist() == [2]
    assert reference.query_rows(cols, schema, q_range).tolist() == [
        False, False, False, True, False]
    q_in = ((("in", 1, (2,)),),)
    assert reference.query_blocks(d, schema, layout, q_in).tolist() == [1]
    q_adv = ((("adv", 0, "<", 2),),)  # a cut of the layout: block 1 fails
    assert reference.query_blocks(d, schema, layout, q_adv).tolist() == [0, 2]
    q_other = ((("adv", 2, "<", 0),),)  # no bit for it: nothing ruled out
    assert reference.query_blocks(d, schema, layout, q_other).tolist() == [0, 1, 2]
    q_or = ((("range", 0, "<", 1),), (("in", 1, (2,)),))
    assert reference.query_blocks(d, schema, layout, q_or).tolist() == [1]
    assert reference.query_rows(cols, schema, q_or).tolist() == [
        False, True, False, False, False]


def _set(key, index, value):
    def plant(layout):
        layout[key] = layout[key].copy()
        layout[key][index] = value
    return plant


@pytest.mark.parametrize("plant,faulty", [
    (lambda layout: None, False),
    (_set("node_leaf", 4, 0), True),  # two leaves say block 0, none 1
    (_set("node_left", 1, 0), True),  # a child that leads back to the root
    (_set("cut_point", 0, 11), True),  # a range cut past its domain
    (_set("cut_dim", 1, 0), True),  # an IN cut on a numeric column
    (lambda layout: layout.update(depth=3), True),
], ids=["sound", "leaf-twice", "cycle", "range-outside", "in-on-numeric",
        "depth"])
def test_reference_checks_the_layout_snapshot_on_its_own(plant, faulty):
    schema, layout = _toy_layout()
    plant(layout)
    assert bool(reference.layout_faults(layout, schema)) == faulty


def test_the_layout_is_sound_and_the_same_for_every_seed():
    from qdbench.system import Program

    cfg, _ = _tiny(("tpch_sf1", "bulk_load"))
    a, b = (cells.set_up_layout(SPEC, cfg, seed, Program(), lambda m: None)
            for seed in (5, 2**31 + 11))
    assert a["layout_faults"] == b["layout_faults"] == 0
    assert cells.fingerprint(a["layout"]) == cells.fingerprint(b["layout"])
    assert a["build"] == b["build"]
    assert not np.array_equal(a["table"], b["table"])


# ---------------------------------------------------------------------------
# the device check, and whole runs at a tiny size
# ---------------------------------------------------------------------------
def test_run_refuses_to_measure_without_a_tpu(capsys):
    spec = importlib.util.spec_from_file_location("qdbench_run",
                                                  BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    assert run.main(["--workload", "tpch_sf1.ingest", "--seed", "3",
                     "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "TPU" in out.err


def _tiny(pair: tuple):
    config, traffic = pair
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text()) | {
        "rows": 12000, "sample_rows": 600, "min_block": 40, "batch": 2048}
    mix = SPEC.traffic(traffic)
    if mix["kind"] == "bulk_load":
        mix |= {"call_bytes": 2048 * 4 * cfg["columns"] * 2, "check_calls": 2}
    else:
        mix |= {"rate_qps": 100, "warm_seconds": 0.3, "check_requests": 0}
    return cfg, mix


def _run(pair: tuple, program=None, seed: int = 2**31 + 7):
    from qdbench.system import Program

    cfg, mix = _tiny(pair)
    return cells.run(SPEC, cfg, mix, seed, 0.4, False, program or Program(),
                     lambda m: None)


CELLS = sorted({(w["config"], w["traffic"]) for w in SPEC.data["workloads"]})


@pytest.mark.parametrize("pair", CELLS, ids="+".join)
def test_a_tiny_run_is_correct(pair):
    out = _run(pair)
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted > 0
    assert out.layer["compiles"] == 0
    for w in SPEC.data["workloads"]:
        if (w["config"], w["traffic"]) == pair:
            assert set(out.end_to_end) >= {
                m["name"] for m in SPEC.end_to_end(w["name"])}
    assert all(v > 0 for v in out.end_to_end.values())


@pytest.mark.parametrize("pair", CELLS, ids="+".join)
def test_the_control_is_not_correct(pair):
    from qdbench.variants import StaleDescriptions

    assert not _run(pair, StaleDescriptions()).correct


def _fault_state_unchanged(monkeypatch):
    from repro.core.qdtree import IncrementalTightener

    monkeypatch.setattr(IncrementalTightener, "apply", lambda self: None)


def _fault_half_batch(monkeypatch):
    from repro.engine.engine import LayoutEngine

    real = LayoutEngine.fused_step

    def half(self, records, *a, **kw):
        return real(self, records[: records.shape[0] // 2], *a, **kw)

    monkeypatch.setattr(LayoutEngine, "fused_step", half)


def _fault_count_altered(monkeypatch):
    from repro.engine.engine import LayoutEngine

    real = LayoutEngine.fused_step

    def altered(self, records, *a, **kw):
        bids, part = real(self, records, *a, **kw)
        part.counts[int(np.argmax(part.counts))] += 1
        return bids, part

    monkeypatch.setattr(LayoutEngine, "fused_step", altered)


def _fault_list_altered(monkeypatch):
    from repro.engine.engine import LayoutEngine

    real = LayoutEngine.route_queries

    def altered(self, *a, **kw):
        lists = real(self, *a, **kw)
        lists[0] = lists[0][1:] if len(lists[0]) else np.array([0], np.int32)
        return lists

    monkeypatch.setattr(LayoutEngine, "route_queries", altered)


def _fault_half_dispatch(monkeypatch):
    from repro.serve import QueryServer

    real = QueryServer._dispatch

    def half(self, tickets):
        real(self, tickets[: (len(tickets) + 1) // 2])

    monkeypatch.setattr(QueryServer, "_dispatch", half)


def _fault_layout_leaf_twice(monkeypatch):
    from qdbench.system import Program

    real = Program.layout

    def twice(self, svc, schema):
        layout = real(self, svc, schema)
        leaf = layout["node_leaf"]
        first, second = np.nonzero(leaf >= 0)[0][:2]
        leaf[second] = leaf[first]
        return layout

    monkeypatch.setattr(Program, "layout", twice)


LOAD, SERVE = ("tpch_sf1", "bulk_load"), ("tpch_sf1", "tpch_adhoc_fresh")


@pytest.mark.parametrize("pair,fault", [
    (LOAD, _fault_state_unchanged),
    (LOAD, _fault_half_batch),
    (LOAD, _fault_count_altered),
    (SERVE, _fault_list_altered),
    (SERVE, _fault_half_dispatch),
    (LOAD, _fault_layout_leaf_twice),
], ids=["ingest-state-unchanged", "ingest-half-batch", "ingest-count-altered",
        "serve-list-altered", "serve-half-dispatch", "layout-leaf-twice"])
def test_a_planted_fault_is_not_correct(pair, fault, monkeypatch):
    from qdbench.system import import_program

    import_program()
    fault(monkeypatch)
    if fault is _fault_half_dispatch:
        monkeypatch.setattr(cells, "LATE_WAIT_S", 0.5)
    assert not _run(pair).correct
