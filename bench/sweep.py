"""Find the highest rate a serving cell sustains, by a sweep on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 5 \
        --rates 250,500,1000,2000,4000

Builds the cell's set-up once, then offers its traffic mix open-loop at
each rate in turn for ``--seconds`` and prints one JSON line per rate:
latency percentiles from the due time, the generator's lateness, the
requests refused at the door, and whether the backlog grew (the median
latency of the last fifth of the requests more than twice that of the
first fifth plus 5 ms, or any request refused or lost).  A cell's rate is
then fixed at about four fifths of the highest rate without a growing
backlog, and written into its traffic file.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from qdbench import cells, spec as speclib, traffic as tr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    spec = speclib.Spec()
    cell = spec.cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(spec.root / ".jax_cache"))
    from qdbench.system import Program

    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    program = Program()
    lay = cells.set_up_layout(spec, cfg, args.seed, program, print)
    svc, schema = lay["svc"], lay["schema"]
    stream = tr.IngestStream(lay["table"], cfg["batch"], cfg["batch"])
    program.ingest(svc, stream.whole_table())
    server = program.server(svc)
    off = cells.Tracer(False)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            seed = args.seed + 1000 * (k + 1)
            due = tr.schedule(rate, args.seconds, seed)
            plain = tr.requests(spec, mix, cfg, schema, len(due), seed)
            if k == 0:
                widest = max(len(q) for q in plain)
                program.warm_server(server, schema,
                                    [q for q in plain if len(q) == widest][:64])
            before = program.counters(server)
            got = cells.open_loop(program, server, plain, due, off)
            after = program.counters(server)
            ms = got.latency_s * 1e3
            fifth = max(len(ms) // 5, 1)
            first, last = np.median(ms[:fifth]), np.median(ms[-fifth:])
            refused, lost = got.refused, got.lost
            late = (got.sent - (got.t0 + due)) * 1e3
            print(json.dumps({
                "rate_qps": rate, "requests": len(due),
                "p50_ms": float(np.percentile(ms, 50)),
                "p95_ms": float(np.percentile(ms, 95)),
                "p99_ms": float(np.percentile(ms, 99)),
                "first_fifth_median_ms": float(first),
                "last_fifth_median_ms": float(last),
                "late_p99_ms": float(np.percentile(late, 99)),
                "refused": refused, "lost": lost,
                "growing": bool(last > 2 * first + 5 or refused or lost),
                "counters": {k2: after[k2] - before[k2] for k2 in after},
            }), flush=True)
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
