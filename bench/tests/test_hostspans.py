"""CPU tests of the program-span reduction (``qdbench.hostspans``), the
span report of a traced run, and the queue-wait reader."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from qdbench import hostspans, spec as speclib  # noqa: E402

SPEC = speclib.Spec()


def _module(name: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_host_spans_filter_clip_and_sum():
    events = [
        (0, 400, "engine.stage"),  # starts before the window: clipped
        (500, 700, "engine.stage"),
        (700, 1500, "engine.device_wait"),  # ends after it: clipped
        (1100, 1300, "engine.fold"),  # wholly outside: left out
        (200, 300, "serve.probe"),
        (100, 900, "bench.ingest_call"),  # not the program's
        (300, 350, "Transpose"),
    ]
    spans = hostspans.host_spans((100, 1000), events)
    assert set(spans) == {"engine.stage", "engine.device_wait", "serve.probe"}
    assert spans["engine.stage"]["count"] == 2
    assert spans["engine.stage"]["total_s"] == pytest.approx(500e-9)
    assert spans["engine.device_wait"] == {"count": 1,
                                           "total_s": pytest.approx(300e-9)}
    assert spans["serve.probe"]["total_s"] == pytest.approx(100e-9)


def test_covered_time_is_a_union_inside_the_window():
    events = [(0, 300, "serve.coalesce"), (200, 500, "serve.dispatch"),
              (700, 1200, "serve.coalesce"), (550, 650, "serve.probe")]
    got = hostspans.covered_s((100, 1000), events,
                              ("serve.coalesce", "serve.dispatch"))
    assert got == pytest.approx((400 + 300) * 1e-9)


def test_report_of_an_ingest_window():
    lines = [[(0, 1000, "engine.ingest"), (0, 20, "engine.stage"),
              (20, 90, "engine.device_wait"), (90, 100, "engine.fold"),
              (100, 130, "engine.stage"), (130, 190, "engine.device_wait"),
              (190, 200, "engine.fold"), (200, 210, "engine.publish")]]
    r = hostspans.report((0, 1000), lines, {"kind": "ingest"})
    assert r["engine.stage_ms"] == pytest.approx(25e-6)
    assert r["engine.device_wait_ms"] == pytest.approx(65e-6)
    assert r["engine.fold_ms"] == pytest.approx(10e-6)
    assert r["engine.publish_ms"] == pytest.approx(10e-6)
    empty = hostspans.report((0, 1000), [], {"kind": "ingest"})
    assert empty["engine.stage_ms"] is None


def test_report_of_a_serving_window():
    dispatcher = [(0, 200, "serve.coalesce"), (200, 600, "serve.dispatch"),
                  (200, 300, "serve.probe"), (300, 500, "serve.route"),
                  (500, 580, "serve.finish"), (600, 950, "serve.coalesce")]
    other = [(0, 1000, "serve.probe")]  # a sync caller's line
    counters = {"queries_served": 4, "queries_routed": 2}
    r = hostspans.report((0, 1000), [other, dispatcher],
                         {"kind": "serve", "counters": counters})
    assert r["serve.dispatcher_busy_share"] == pytest.approx(40.0)
    assert r["serve.probe_us_per_query"] == pytest.approx(1100e-3 / 4)
    assert r["serve.route_us_per_miss"] == pytest.approx(200e-3 / 2)
    assert r["serve.finish_us_per_query"] == pytest.approx(80e-3 / 4)
    assert r["dispatcher_line_covered_share"] == pytest.approx(95.0)
    assert r["dispatch_covered_share"] == pytest.approx(100.0 * 1380 / 400)
    none = hostspans.report((0, 1000), [], {"kind": "serve", "counters": {}})
    assert none["serve.probe_us_per_query"] is None
    assert none["dispatch_covered_share"] is None


def test_queue_wait_reader_reads_the_counter_or_nothing():
    read = SPEC.reader("serve.queue_wait_ms")
    c = {"queries_served": 8, "queue_wait_us": 20000}
    assert read({"kind": "serve", "counters": c}, {}) == pytest.approx(2.5)
    # a program without the counter (an older one) reports nothing
    assert read({"kind": "serve", "counters": {"queries_served": 8}}, {}) \
        is None
    assert read({"kind": "serve", "counters": c | {"queries_served": 0}},
                {}) is None
    assert read({"kind": "ingest"}, {}) is None


def _tiny(traffic: str):
    cfg = json.loads((BENCH / "configs" / "tpch_sf1.json").read_text()) | {
        "rows": 12000, "sample_rows": 600, "min_block": 40, "batch": 2048}
    mix = SPEC.traffic(traffic)
    if mix["kind"] == "bulk_load":
        mix |= {"call_bytes": 2048 * 4 * cfg["columns"] * 2, "check_calls": 2}
    else:
        mix |= {"rate_qps": 100, "warm_seconds": 0.3, "check_requests": 0}
    return cfg, mix


@pytest.mark.parametrize("traffic", ["bulk_load", "tpch_adhoc_fresh"])
def test_a_tiny_traced_run_reports_the_program_spans(traffic):
    from qdbench.system import Program

    report = _module("qdbench_span_report", BENCH / "span_report.py")
    cfg, mix = _tiny(traffic)
    out, spans = report.measure(SPEC, cfg, mix, 2**31 + 5, 0.4, Program(),
                                lambda m: None)
    assert out.correct, out.checks
    assert out.layer["trace"].window_s > 0
    if traffic == "bulk_load":
        n = spans["host_spans"]["engine.stage"]["count"]
        assert n == spans["host_spans"]["engine.fold"]["count"] > 0
        assert all(spans[f"engine.{m}_ms"] > 0 for m in
                   ("stage", "device_wait", "fold", "publish"))
    else:
        assert spans["host_spans"]["serve.dispatch"]["count"] == \
            out.layer["counters"]["dispatches"]
        assert spans["dispatcher_line_covered_share"] > 90.0
        assert spans["dispatch_covered_share"] > 90.0
        assert spans["serve.probe_us_per_query"] > 0
