"""The program's own spans in a traced window, reduced per name.

The program opens ``jax.profiler.TraceAnnotation`` spans whose names
start with :data:`PREFIXES` (``repro.spans`` lists them).  They land on
the host plane of the same ``.xplane.pb`` that ``qdbench.trace`` reduces,
on the profiler's clock, one line per host thread.  This module reduces
them to a count and a total per name, clipped to the window, and derives
the per-layer numbers that read them (:func:`report`).

``qdbench.trace.Summary`` does not carry these totals, so the cells'
result lines do not report them: ``bench/span_report.py`` runs a cell
traced and prints them beside its result line.
"""

from __future__ import annotations

import glob
import os

import numpy as np

from qdbench import trace as tracing

PREFIXES = ("engine.", "serve.")


def read(trace_dir: str) -> tuple:
    """``(window, lines)`` of the newest ``.xplane.pb`` under
    ``trace_dir``: the window annotation's ``(start, end)`` in ns, and one
    list of ``(start, end, name)`` per host line holding a program span."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(files[-1])
    window, lines = None, []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = []
            for e in line.events:
                if e.name == tracing.WINDOW:
                    window = (e.start_ns, e.end_ns)
                elif e.name.startswith(PREFIXES):
                    evs.append((e.start_ns, e.end_ns, e.name))
            if evs:
                lines.append(evs)
    if window is None:
        raise ValueError(f"the trace holds no {tracing.WINDOW!r} annotation")
    return window, lines


def host_spans(window: tuple, events: list) -> dict:
    """``{name: {"count", "total_s"}}`` over the events (``(start, end,
    name)`` in ns) that bear a program prefix and overlap the window,
    each clipped to it."""
    lo, hi = window
    out = {}
    for s, e, name in events:
        d = min(e, hi) - max(s, lo)
        if not name.startswith(PREFIXES) or d <= 0:
            continue
        agg = out.setdefault(name, {"count": 0, "total_s": 0.0})
        agg["count"] += 1
        agg["total_s"] += d * 1e-9
    return out


def covered_s(window: tuple, events: list, names: tuple) -> float:
    """Seconds of the window that the union of the named events covers."""
    iv = np.array([(s, e) for s, e, n in events if n in names],
                  np.float64).reshape(-1, 2)
    u = tracing.union(tracing.clip(iv, *window))
    return float((u[:, 1] - u[:, 0]).sum()) * 1e-9


def _mean_ms(spans: dict, name: str):
    s = spans.get(name)
    return s["total_s"] / s["count"] * 1e3 if s and s["count"] else None


def report(window: tuple, lines: list, layer: dict) -> dict:
    """The span metrics of one traced run, with the checks of how far
    the spans cover the work they split.  ``layer`` is what the cell's
    readers read (its counters, for a serving cell)."""
    spans = host_spans(window, [e for evs in lines for e in evs])
    window_s = (window[1] - window[0]) * 1e-9
    out = {"host_spans": spans}
    if layer.get("kind") == "ingest":
        for m in ("stage", "device_wait", "fold", "publish"):
            out[f"engine.{m}_ms"] = _mean_ms(spans, f"engine.{m}")
        return out
    c = layer.get("counters") or {}

    def per(name, count):
        s = spans.get(name)
        return s["total_s"] / count * 1e6 if s and count else None

    dispatch = spans.get("serve.dispatch", {"total_s": 0.0})["total_s"]
    out["serve.dispatcher_busy_share"] = 100.0 * dispatch / window_s
    out["serve.probe_us_per_query"] = per("serve.probe",
                                          c.get("queries_served"))
    out["serve.route_us_per_miss"] = per("serve.route",
                                         c.get("queries_routed"))
    out["serve.finish_us_per_query"] = per("serve.finish",
                                           c.get("queries_served"))
    # the dispatcher thread's line: the one that holds the dispatches
    line = max(lines, default=[], key=lambda evs: sum(
        n == "serve.dispatch" for _, _, n in evs))
    out["dispatcher_line_covered_share"] = 100.0 * covered_s(
        window, line, ("serve.coalesce", "serve.dispatch")) / window_s
    inner = sum(spans.get(n, {"total_s": 0.0})["total_s"]
                for n in ("serve.probe", "serve.route", "serve.finish"))
    out["dispatch_covered_share"] = (100.0 * inner / dispatch
                                     if dispatch else None)
    return out
