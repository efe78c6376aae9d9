"""What the program's spans cost with no profiler trace running.

    python bench/span_cost.py [--reps N]

Opens and closes, ``--reps`` times over, the spans one serve dispatch
opens (``serve.coalesce``, ``serve.dispatch`` with its argument,
``serve.probe``, ``serve.route`` with its argument, ``serve.finish``) and
those one ingest batch opens (``engine.stage``, ``engine.device_wait``,
``engine.fold``), with nothing inside them, and prints one JSON line of
microseconds per dispatch and per batch: the best of five rounds.
"""

from __future__ import annotations

import argparse
import json
import time

from qdbench.system import import_program


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=100_000)
    args = ap.parse_args(argv)
    import_program()
    from repro import spans

    span = spans.span

    def dispatch():
        with span(spans.SERVE_COALESCE):
            pass
        with span(spans.SERVE_DISPATCH, n=4):
            with span(spans.SERVE_PROBE):
                pass
            with span(spans.SERVE_ROUTE, misses=3):
                pass
            with span(spans.SERVE_FINISH):
                pass

    def batch():
        with span(spans.ENGINE_STAGE):
            pass
        with span(spans.ENGINE_DEVICE_WAIT):
            pass
        with span(spans.ENGINE_FOLD):
            pass

    out = {}
    for name, fn in (("us_per_dispatch", dispatch), ("us_per_batch", batch)):
        best = float("inf")
        for _ in range(5):
            t = time.perf_counter()
            for _ in range(args.reps):
                fn()
            best = min(best, time.perf_counter() - t)
        out[name] = best / args.reps * 1e6
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
