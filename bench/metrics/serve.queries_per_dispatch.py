"""Mean coalesced batch of the window: ``ServerCounters.queries_served``
over ``dispatches``."""


def read(layer, peaks):
    c = layer.get("counters")
    if not c or not c["dispatches"]:
        return None
    return c["queries_served"] / c["dispatches"]
