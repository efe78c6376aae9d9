"""The one traffic generator.  A traffic mix is a JSON file of parameters
under ``bench/traffic/``; this module turns one, a configuration and a
seed into the exact work a run offers.

Two kinds of mix exist:

``bulk_load``   back-to-back ingest calls, each ``call_bytes`` of rows cut
                into the configuration's batches (whole batches only),
                streaming the table over and over from its first row.
``open_loop``   requests due on a Poisson-like schedule at ``rate_qps``,
                sent whether or not earlier ones have finished.  Every
                seed gets the same number of requests, the same multiset
                of gaps and the same mix of templates, in another
                order, so the seed changes which work arrives when and
                never how much.  The mix's ``requests`` names the draw
                of the queries, ``requests/<requests>.py`` under
                ``bench/``, whose ``requests(spec, traffic, cfg, schema,
                n, seed)`` gives them in arrival order.
"""

from __future__ import annotations

import time

import numpy as np


def rows_per_call(traffic: dict, cfg: dict, n_cols: int) -> int:
    """Rows of one ingest call: the whole batches that fit ``call_bytes``
    of 4-byte codes."""
    batch = cfg["batch"]
    per = traffic["call_bytes"] // (4 * n_cols) // batch * batch
    if per < batch:
        raise ValueError("call_bytes holds less than one batch")
    return per


class IngestStream:
    """The table, cut into ``batch``-row slices, repeated without end.

    Call ``i`` takes slices ``i * per_call`` onwards.  Each pull of a batch
    is stamped on the host clock (``pulls``), which is how the benchmark
    sees the engine's per-batch pace from outside the program.
    """

    def __init__(self, table: np.ndarray, batch: int, per_call: int):
        self.table, self.batch, self.per_call = table, batch, per_call
        self.pulls: list[tuple[int, float]] = []  # (call, perf_counter)

    def rows_of(self, call: int) -> np.ndarray:
        """Row indices of call ``call``, in stream order."""
        n = self.table.shape[0]
        return (call * self.per_call + np.arange(self.per_call)) % n

    def batches(self, call: int):
        n, b = self.table.shape[0], self.batch
        start = call * self.per_call
        for s in range(start, start + self.per_call, b):
            a = s % n
            if a + b <= n:
                out = self.table[a:a + b]
            else:  # the slice that wraps round the table's end
                out = self.table[(a + np.arange(b)) % n]
            self.pulls.append((call, time.perf_counter()))
            yield out

    def whole_table(self):
        """Every row once, in ``batch``-row slices (the last one short)."""
        for s in range(0, self.table.shape[0], self.batch):
            yield self.table[s:s + self.batch]


def schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds) of ``round(rate * seconds)`` requests.

    The gaps are the exponential distribution's quantiles at the midpoints
    of ``n`` equal slices, shuffled by the seed and scaled to fill the
    window exactly: a Poisson process's spread of gaps, with the same
    count and gaps for every seed.
    """
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    np.random.default_rng(seed).shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


def apportion(weights: np.ndarray, n: int) -> np.ndarray:
    """Integer counts summing to ``n`` in proportion to ``weights``
    (largest remainders first, ties to the lower index)."""
    exact = weights / weights.sum() * n
    counts = np.floor(exact).astype(np.int64)
    rest = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


def requests(spec, traffic: dict, cfg: dict, schema, n: int,
             seed: int) -> list:
    """The ``n`` plain queries of an open-loop mix, in arrival order."""
    draw = spec.load("requests", traffic["requests"])
    return draw.requests(spec, traffic, cfg, schema, n, seed)
