"""Run the control of a cell beside the program, on the chip, at the
cell's own size.

    python bench/control.py --workload <cell> --seeds 101,102,103 --seconds 3

For each seed, in one process, the cell runs once with the program and
once with the control (``qdbench.variants.StaleDescriptions``) in its
place, each with a short window at the cell's own load, and prints one
JSON line with the numbers each compared against the reference.  The
program's readings are a cell's lower readings, the control's its upper
ones; ``correct`` must come out false for the control.
"""

from __future__ import annotations

import argparse
import json
import sys

from qdbench import cells, spec as speclib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    spec = speclib.Spec()
    cell = spec.cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(spec.root / ".jax_cache"))
    from qdbench.system import Program
    from qdbench.variants import StaleDescriptions

    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, program in (("program", Program()),
                              ("control", StaleDescriptions())):
            out = cells.run(spec, cfg, mix, seed, args.seconds, False,
                            program, lambda m: None)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "side": name,
                "correct": out.correct,
                "checks": {k: v for k, (v, _) in out.checks.items()},
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
