"""Run one cell of the qd-tree layout service's benchmark on this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell's configuration, traffic mix
and metrics come from ``BENCHMARK.json`` and the files it names under
``bench/``.  The run refuses to measure (exit code 2, no result line)
unless JAX finds a TPU with as many chips as the cell asks for.

Standard output carries progress lines (device, set-up phases, generator
lateness, peak memory) and, last, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end ones, or with
``--trace 1`` the per-layer ones), ``device``, with ``--trace 1`` a
``breakdown`` of the traced window, and ``checks``: every number
compared with the reference beside its limit.  The checks are also the
last lines of standard error.
"""

from __future__ import annotations

import argparse
import json
import sys

from qdbench import cells, spec as speclib, work


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def device_or_none(chips: int):
    """The devices to measure on, or None where there is no TPU with
    ``chips`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        return None
    return devs


def result_line(out: cells.Outcome, spec, cell: str, traced: bool,
                devs, peaks: dict) -> dict:
    metrics = {}
    if traced:
        for m in spec.per_layer(cell):
            value = spec.reader(m["name"])(out.layer, peaks)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec.end_to_end(cell):
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if traced:
        summary = out.layer["trace"]
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.device_ops,
                             "idle_gaps": summary.idle_gaps}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = speclib.Spec()
    cell = spec.cell(args.workload)
    devs = device_or_none(cell["chips"])
    if devs is None:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found none or too few", file=sys.stderr)
        return 2
    peaks = work.peaks(devs[0].device_kind)
    import jax

    cache = spec.root / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device {devs[0].device_kind} x{len(devs)}, platform "
        f"{devs[0].platform}; compile cache {cache}")

    from qdbench.system import Program

    out = cells.run(spec, spec.config(cell["config"]),
                    spec.traffic(cell["traffic"]), args.seed, args.seconds,
                    bool(args.trace), Program(), log)
    log(f"peak device memory {out.memory_peak_bytes} bytes")
    line = result_line(out, spec, args.workload, bool(args.trace), devs, peaks)
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
