"""One run of one cell: set-up, the measured window, and the comparison
with the plain reference.

Set-up (timed as ``setup_s``) learns the layout from a bootstrap sample
and build queries made from the configuration's ``layout_seed``, so every
seed drives the same tree and the same device shapes; it makes the table
from the run's seed, and warms every shape the window will use by running
the cell's own traffic.  The window then runs the traffic mix.  Only
after the window has closed and the device's peak memory has been read
does the reference recompute what the window's answers should have been.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import queue
import shutil
import tempfile
import threading
import time
from typing import Callable, Optional

import numpy as np

from qdbench import datagen, reference, trace as tracing, traffic as tr

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
LATE_WAIT_S = 60.0  # how long past the window an answer may still come


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    setup_s: float
    end_to_end: dict  # metric name -> value
    checks: dict  # check name -> (value, limit)
    layer: dict  # what the per-layer readers read, the trace among it
    memory_peak_bytes: Optional[int]

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


class CompileCounter:
    """Counts JAX's backend compilations, stamped on the host clock."""

    def __init__(self):
        import jax

        self.stamps: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.stamps.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(a <= s <= b for s in self.stamps)


class Tracer:
    """The profiler around a window, when the run is traced."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="qdbench-trace-") if on else None

    @contextlib.contextmanager
    def window(self):
        if not self.on:
            yield
            return
        import jax

        jax.profiler.start_trace(self.dir, profiler_options=tracing.options())
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def reduce(self) -> Optional[tracing.Summary]:
        if not self.on:
            return None
        try:
            return tracing.read(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class GcPauses:
    """The garbage collector's pauses, stamped on the host clock, so a
    stalled window can be told from a collection that stalled it."""

    def __init__(self):
        self.spans: list[tuple[float, float]] = []
        self._start = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.spans.append((self._start, time.perf_counter()))

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def between(self, a: float, b: float) -> str:
        d = [e - s for s, e in self.spans if a <= s <= b]
        return (f"{len(d)} collections, {sum(d) * 1e3:.3f} ms in all, "
                f"longest {max(d, default=0.0) * 1e3:.3f} ms")


def _peak_memory() -> Optional[int]:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def fingerprint(layout: dict) -> str:
    """A short digest of a layout's topology and cut table, so a run's
    log shows whether two runs drove the same tree."""
    h = hashlib.sha1()
    for k in ("node_cut", "node_left", "node_right", "node_leaf",
              "cut_kind", "cut_dim", "cut_point", "cut_mask", "cut_adv"):
        h.update(np.ascontiguousarray(layout[k]).tobytes())
    h.update(repr(layout["adv"]).encode())
    return h.hexdigest()[:12]


def set_up_layout(spec, cfg: dict, seed: int, program,
                  log: Callable) -> dict:
    """Table, build queries and layout of a configuration, from the files
    ``spec`` finds by the names the configuration gives.  The table
    comes from the run's seed; the layout is learned on a bootstrap sample
    of ``sample_rows`` rows and the build queries, both made from the
    configuration's ``layout_seed``, so it is the same for every seed."""
    t = time.perf_counter()
    schema, table = datagen.make_table(spec, cfg["table"], cfg["rows"], seed)
    _, sample = datagen.make_table(spec, cfg["table"], cfg["sample_rows"],
                                   cfg["layout_seed"])
    build = datagen.make_queries(spec, cfg["query_family"], schema,
                                 cfg["build_queries"], cfg["layout_seed"])
    t_gen = time.perf_counter() - t
    t = time.perf_counter()
    svc = program.build(sample, schema, build, cfg, cfg["layout_seed"])
    layout = program.layout(svc, schema)
    t_build = time.perf_counter() - t
    faults = reference.layout_faults(layout, schema)
    log(f"table {table.shape[0]} x {table.shape[1]} int32 "
        f"({table.nbytes / 2**20:.1f} MiB), {len(build)} build queries; "
        f"{cfg['strategy']} layout on {cfg['sample_rows']} sample rows: "
        f"{layout['n_leaves']} blocks, depth {layout['depth']}, "
        f"{len(layout['adv'])} advanced cuts, fingerprint "
        f"{fingerprint(layout)}; layout faults {faults}")
    return {"schema": schema, "table": table, "build": build, "svc": svc,
            "layout": layout, "layout_faults": len(faults),
            "phases": {"generate": t_gen, "build": t_build}}


def _phases(log: Callable, phases: dict) -> None:
    log("set-up phases: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in phases.items()))


# ---------------------------------------------------------------------------
# bulk load
# ---------------------------------------------------------------------------
def run_ingest(spec, cfg: dict, mix: dict, seed: int, seconds: float,
               traced: bool, program, log: Callable) -> Outcome:
    counter, pauses = CompileCounter(), GcPauses()
    t0 = time.perf_counter()
    lay = set_up_layout(spec, cfg, seed, program, log)
    svc, table, layout, schema = (lay["svc"], lay["table"], lay["layout"],
                                  lay["schema"])
    per_call = tr.rows_per_call(mix, cfg, table.shape[1])
    stream = tr.IngestStream(table, cfg["batch"], per_call)
    t = time.perf_counter()
    program.ingest(svc, stream.batches(0))  # the same traffic, once
    lay["phases"]["warm"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t0
    _phases(log, lay["phases"])
    log(f"calls of {per_call} rows ({per_call // cfg['batch']} batches of "
        f"{cfg['batch']}, {per_call * table.shape[1] * 4 / 2**20:.1f} MiB)")

    tracer = Tracer(traced)
    calls, failed = [], 0
    with tracer.window():
        start = time.perf_counter()
        i = 1
        while True:
            try:
                with tracer.span("bench.ingest_call"):
                    counts = program.ingest(svc, stream.batches(i))
                calls.append((i, counts, program.descriptions(svc),
                              time.perf_counter() - start))
            except Exception as e:  # noqa: BLE001 - counted and reported
                failed += 1
                log(f"ingest call {i} failed: {e!r}")
                break
            i += 1
            if time.perf_counter() - start >= seconds:
                break
        end = time.perf_counter()
    window_s = end - start
    memory = _peak_memory()
    rows = per_call * len(calls)
    log(f"window {window_s:.3f} s: {len(calls)} calls, {rows} rows published;"
        f" calls returned at s {[round(c[3], 3) for c in calls]}; gc "
        f"{pauses.between(start, end)}")
    pauses.close()
    summary = tracer.reduce()

    t = time.perf_counter()
    pick = np.random.default_rng(seed).permutation(len(calls))
    count_diff = desc_diff = 0
    checked = sorted(pick[: mix["check_calls"]])
    for j in checked:
        i, counts, desc, _ = calls[j]
        cols = reference.columns(table[stream.rows_of(i)])
        want = reference.describe(layout, schema, cols,
                                  reference.route(layout, cols))
        count_diff += int((counts != want["counts"]).sum())
        differs = np.zeros(layout["n_leaves"], bool)
        for k in ("lo", "hi", "cat", "adv"):
            differs |= (desc[k] != want[k]).reshape(layout["n_leaves"], -1).any(1)
        desc_diff += int(differs.sum())
    log(f"reference over calls {[calls[j][0] for j in checked]} "
        f"({time.perf_counter() - t:.3f} s)")

    pulls = {}
    for call, stamp in stream.pulls:
        pulls.setdefault(call, []).append(stamp)
    batch_gaps = [g for call, s in pulls.items() if call > 0
                  for g in np.diff(s)]
    return Outcome(
        attempted=len(calls) + failed,
        failed=failed,
        setup_s=setup_s,
        end_to_end={"ingest_rows_per_s": rows / window_s,
                    "setup_s": setup_s},
        checks={
            "layout_faults": (lay["layout_faults"], 0),
            "calls_failed": (failed, 0),
            "calls_unchecked": (int(not checked), 0),
            "count_diff_blocks": (count_diff, 0),
            "desc_diff_blocks": (desc_diff, 0),
        },
        layer={"kind": "ingest", "rows": rows, "cols": table.shape[1],
               "depth": layout["depth"], "batch_gaps_s": batch_gaps,
               "compiles": counter.between(start, end), "trace": summary},
        memory_peak_bytes=memory,
    )


# ---------------------------------------------------------------------------
# open-loop query serving
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Answers:
    """What the client saw of one open-loop stream, on its own monotonic
    clock: when each request was due (``t0 + due``), sent, and answered
    (NaN where it was refused at the door or never answered), and the
    block ids of each answer."""

    t0: float
    due: np.ndarray
    sent: np.ndarray
    done: np.ndarray
    lists: list
    refused: int

    @property
    def lost(self) -> int:
        """Admitted requests that never got an answer."""
        return int(np.isnan(self.done).sum()) - self.refused

    @property
    def latency_s(self) -> np.ndarray:
        """Each request's latency from its due time; a refused or lost
        one reads as having waited past the late limit."""
        missing = self.t0 + self.due[-1] + LATE_WAIT_S + 1.0
        return np.where(np.isnan(self.done), missing, self.done) - (
            self.t0 + self.due)


def open_loop(program, server, plain: list, due: np.ndarray,
              tracer: Tracer) -> Answers:
    """Send each request at its due time, making its query as it is sent,
    while a collector thread takes the answers in the order sent and
    stamps each as it returns.  An answer that came back before an earlier
    one reads as late as that one, which errs on the slow side.  Of an
    answer the program gives only its block ids."""
    n = len(plain)
    sent, done, lists = np.empty(n), np.full(n, np.nan), [None] * n
    inbox: queue.SimpleQueue = queue.SimpleQueue()
    t0 = time.monotonic() + 0.001
    deadline = t0 + due[-1] + LATE_WAIT_S

    def collect() -> None:
        while (item := inbox.get()) is not None:
            i, ticket = item
            bids = program.outcome(ticket,
                                   max(deadline - time.monotonic(), 0.0))
            if bids is not None:
                done[i] = time.monotonic()
                lists[i] = bids

    collector = threading.Thread(target=collect, name="bench-collector",
                                 daemon=True)
    collector.start()
    refused = 0
    try:
        with tracer.span("bench.send_loop"):
            for i in range(n):
                wait = t0 + due[i] - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                sent[i] = time.monotonic()
                ticket = program.submit(server, program.query(plain[i]))
                if ticket is None:
                    refused += 1
                else:
                    inbox.put((i, ticket))
    finally:
        inbox.put(None)
        collector.join()
    return Answers(t0, due, sent, done, lists, refused)


def lateness_log(a: Answers) -> str:
    """The generator's lateness (send time past due time), overall and at
    its worst second of the window."""
    late = (a.sent - (a.t0 + a.due)) * 1e3
    sec = a.due.astype(np.int64)
    worst = max(range(sec[-1] + 1), key=lambda k: late[sec == k].max(
        initial=0.0))
    return (f"generator lateness ms p50 {np.percentile(late, 50):.4f} "
            f"p99 {np.percentile(late, 99):.4f} max {late.max():.4f} "
            f"(worst in second {worst} of the window)")


def run_serve(spec, cfg: dict, mix: dict, seed: int, seconds: float,
              traced: bool, program, log: Callable) -> Outcome:
    counter, pauses = CompileCounter(), GcPauses()
    t0 = time.perf_counter()
    lay = set_up_layout(spec, cfg, seed, program, log)
    svc, table, layout, schema = (lay["svc"], lay["table"], lay["layout"],
                                  lay["schema"])
    t = time.perf_counter()
    stream = tr.IngestStream(table, cfg["batch"], cfg["batch"])
    published = program.ingest(svc, stream.whole_table())
    lay["phases"]["ingest"] = time.perf_counter() - t

    t = time.perf_counter()
    rate = mix["rate_qps"]
    warm_due = tr.schedule(rate, mix["warm_seconds"], seed + 1)
    due = tr.schedule(rate, seconds, seed)
    warm_plain = tr.requests(spec, mix, cfg, schema, len(warm_due), seed + 1)
    plain = tr.requests(spec, mix, cfg, schema, len(due), seed)
    lay["phases"]["traffic"] = time.perf_counter() - t

    server = program.server(svc)
    tracer = Tracer(traced)
    try:
        t = time.perf_counter()
        every = plain + warm_plain
        widest = max(len(q) for q in every)
        program.warm_server(server, schema,
                            [q for q in every if len(q) == widest][:64])
        open_loop(program, server, warm_plain, warm_due, Tracer(False))
        lay["phases"]["warm"] = time.perf_counter() - t
        setup_s = time.perf_counter() - t0
        _phases(log, lay["phases"])
        before = program.counters(server)
        with tracer.window():
            start = time.perf_counter()
            got = open_loop(program, server, plain, due, tracer)
            end = time.perf_counter()
        after = program.counters(server)
    finally:
        server.stop()
    memory = _peak_memory()
    summary = tracer.reduce()
    lat, lists = got.latency_s, got.lists
    log(f"{len(due)} requests due at {rate} qps over {seconds} s; "
        f"{lateness_log(got)}; refused {got.refused}, lost {got.lost}; gc "
        f"{pauses.between(start, end)}")
    pauses.close()
    done = [i for i, b in enumerate(lists) if b is not None]
    scanned = sum(int(published[lists[i]].sum()) for i in done)
    share = scanned / (table.shape[0] * len(done)) if done else 1.0

    t = time.perf_counter()
    cols = reference.columns(table)
    leaf = reference.route(layout, cols)
    want = reference.describe(layout, schema, cols, leaf)
    count_diff = int((published != want["counts"]).sum())
    rng = np.random.default_rng(seed)
    check = done if not mix["check_requests"] else sorted(
        rng.permutation(done)[: mix["check_requests"]])
    list_diff = sum(
        not np.array_equal(lists[i], reference.query_blocks(want, schema,
                                                            layout, plain[i]))
        for i in check)
    rows_missed = 0
    for i in sorted(rng.permutation(done)[: mix["filter_requests"]]):
        hit = np.unique(leaf[reference.query_rows(cols, schema, plain[i])])
        rows_missed += int(not np.isin(hit, lists[i]).all())
    log(f"reference: {len(check)} block lists, full-table filter of "
        f"{min(len(done), mix['filter_requests'])} requests "
        f"({time.perf_counter() - t:.3f} s)")

    delta = {k: after[k] - before[k] for k in after}
    return Outcome(
        attempted=len(due),
        failed=got.refused + got.lost,
        setup_s=setup_s,
        end_to_end={
            "query_p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "query_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "scanned_row_share": share,
            "setup_s": setup_s,
        },
        checks={
            "layout_faults": (lay["layout_faults"], 0),
            "requests_lost": (got.lost, 0),
            "requests_unchecked": (int(not done), 0),
            "count_diff_blocks": (count_diff, 0),
            "list_diff_requests": (int(list_diff), 0),
            "rows_missed_requests": (rows_missed, 0),
        },
        layer={"kind": "serve", "counters": delta,
               "compiles": counter.between(start, end), "trace": summary},
        memory_peak_bytes=memory,
    )


KINDS = {"bulk_load": run_ingest, "open_loop": run_serve}


def run(spec, cfg: dict, mix: dict, seed: int, seconds: float, traced: bool,
        program, log: Callable) -> Outcome:
    """One run of a configuration under a mix; ``spec`` finds the files
    they name."""
    return KINDS[mix["kind"]](spec, cfg, mix, seed, seconds, traced, program,
                              log)
