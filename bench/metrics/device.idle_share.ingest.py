"""Share of an ingest cell's traced window in which no operation ran on
the device, in %: 1 - (union of XLA op intervals) / window."""


def read(layer, peaks):
    t = layer.get("trace")
    if layer.get("kind") != "ingest" or t is None:
        return None
    return 100.0 * t.idle_share
