"""Median time in ms between the engine's pulls of consecutive batches
from the benchmark's batch iterator, within one ingest call: the
engine's pace per batch, seen from outside the program."""

import statistics


def read(layer, peaks):
    gaps = layer.get("batch_gaps_s")
    if not gaps:
        return None
    return statistics.median(gaps) * 1e3
