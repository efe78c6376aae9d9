"""Mean wait of a served query between its submission and the start of
its dispatch, in ms, on the server's clock: the window's
``ServerCounters.queue_wait_us`` over ``queries_served``.  Nothing where
the program keeps no such counter."""


def read(layer, peaks):
    c = layer.get("counters")
    if not c or "queue_wait_us" not in c or not c["queries_served"]:
        return None
    return c["queue_wait_us"] / c["queries_served"] / 1e3
