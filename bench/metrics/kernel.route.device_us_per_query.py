"""Device busy time of the traced serving window, in microseconds, over
the queries the engine routed in it (``ServerCounters.queries_routed``):
what one cache miss costs the chip."""


def read(layer, peaks):
    t, c = layer.get("trace"), layer.get("counters")
    if t is None or not c or not c["queries_routed"] or t.busy_s <= 0:
        return None
    return t.busy_s / c["queries_routed"] * 1e6
