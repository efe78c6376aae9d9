"""Run one cell traced and print the program's spans beside its result.

    python bench/span_report.py --workload <cell> --seed <n> --seconds <s>

The run is ``bench/run.py --trace 1``'s: the same set-up, window,
reference and result line, whose ``metrics`` are the cell's per-layer
metrics.  The line gains ``end_to_end``, the traced run's end-to-end
numbers (for what tracing costs), and ``program_spans``: the count and
total of each ``engine.*`` / ``serve.*`` span in the window and the
numbers derived from them (``qdbench.hostspans.report``).  Exit code
2 without a TPU, as ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as bench
from qdbench import cells, hostspans, spec as speclib, trace as tracing, work


def measure(spec, cfg: dict, mix: dict, seed: int, seconds: float, program,
            log) -> tuple:
    """One traced run of a cell; returns its outcome and the span report.

    The run's tracer removes its trace once ``qdbench.trace.read`` has
    reduced it, so the host spans are read in the same call."""
    kept = {}
    reduce = tracing.read

    def read_and_keep(trace_dir):
        kept["window"], kept["lines"] = hostspans.read(trace_dir)
        return reduce(trace_dir)

    tracing.read = read_and_keep
    try:
        out = cells.run(spec, cfg, mix, seed, seconds, True, program, log)
    finally:
        tracing.read = reduce
    return out, hostspans.report(kept["window"], kept["lines"], out.layer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    spec = speclib.Spec()
    cell = spec.cell(args.workload)
    devs = bench.device_or_none(cell["chips"])
    if devs is None:
        print(f"span_report: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found none or too few", file=sys.stderr)
        return 2
    import jax

    cache = spec.root / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from qdbench.system import Program

    out, spans = measure(spec, spec.config(cell["config"]),
                         spec.traffic(cell["traffic"]), args.seed,
                         args.seconds, Program(), bench.log)
    line = bench.result_line(out, spec, args.workload, True, devs,
                             work.peaks(devs[0].device_kind))
    line["end_to_end"] = out.end_to_end
    line["program_spans"] = spans
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
