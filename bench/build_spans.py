"""Trace a configuration's layout build and print the greedy builder's
per-node scoring spans.

    python bench/build_spans.py --config <name> [--seed <n>]

The set-up's table, build queries and layout build
(``qdbench.cells.set_up_layout``) run under the profiler, and one JSON
line gives the build's seconds, the tree, and the count and total of
each of the program's ``build.*`` spans (``repro.spans.BUILD_SCORE``:
the greedy builder's scoring of each node it tries to split).
``bench/span_report.py`` reads the measured window, which holds no
build.  The build runs on the host, so any platform serves; the line
names the one it ran on.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

from qdbench import cells, spec as speclib

PREFIX = "build."


def build_spans(trace_dir: str) -> dict:
    """``{name: {"count", "total_s"}}`` of the ``build.*`` spans on the
    host plane of the newest trace under ``trace_dir``."""
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(max(files,
                                                  key=os.path.getmtime))
    out = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    agg = out.setdefault(e.name, {"count": 0, "total_s": 0.0})
                    agg["count"] += 1
                    agg["total_s"] += (e.end_ns - e.start_ns) * 1e-9
    return out


def measure(spec, cfg: dict, seed: int, program, log) -> dict:
    """The build of ``cfg`` traced: its phases, tree and build spans."""
    import jax

    with tempfile.TemporaryDirectory(prefix="qdbench-build-") as d:
        with jax.profiler.trace(d):
            lay = cells.set_up_layout(spec, cfg, seed, program, log)
        found = build_spans(d)
    layout = lay["layout"]
    return {"phases": lay["phases"], "blocks": layout["n_leaves"],
            "depth": layout["depth"],
            "fingerprint": cells.fingerprint(layout), "build_spans": found}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from qdbench.system import Program

    spec = speclib.Spec()
    line = measure(spec, spec.config(args.config), args.seed, Program(),
                   lambda m: print(f"[build] {m}", flush=True))
    dev = jax.devices()[0]
    line["platform"] = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
