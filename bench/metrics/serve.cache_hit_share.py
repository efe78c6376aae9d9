"""Share of the window's served queries that the result cache answered:
``ServerCounters.queries_cached`` over ``queries_served``, in %."""


def read(layer, peaks):
    c = layer.get("counters")
    if not c or not c["queries_served"]:
        return None
    return 100.0 * c["queries_cached"] / c["queries_served"]
