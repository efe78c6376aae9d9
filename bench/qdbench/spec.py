"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is ``configs/<name>.json`` (the path ``BENCHMARK.json``
gives) and a traffic mix is ``traffic/<name>.json``.  The code a
configuration or a mix names is a module found by :meth:`Spec.load`: its
table is ``tables/<table>.py``, its query family
``families/<query_family>.py``, an open-loop mix's request draw
``requests/<requests>.py``, and a per-layer metric is
``metrics/<name>.py``, all under the spec's ``bench/``.  Adding a cell, a
mix, a metric, a table, a query family or a request draw adds files and
entries; no file here changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class Spec:
    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.bench = self.root / "bench"
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json").read_text())

    def end_to_end(self, cell: str) -> list:
        """The end-to-end metrics the cell reports."""
        return [m for m in self.data["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        """The per-layer metrics the cell reports: those that list it.
        Every per-layer metric lists its cells under ``workloads``."""
        return [m for m in self.data["per_layer"] if cell in m["workloads"]]

    def reader(self, metric: str):
        """The ``read(layer, peaks)`` function of a per-layer metric."""
        return self.load("metrics", metric).read

    def load(self, kind: str, name: str):
        """The module ``bench/<kind>/<name>.py`` of this spec, loaded once
        per process: ``kind`` is ``tables``, ``families``, ``requests`` or
        ``metrics``."""
        return _module(self.bench / kind / f"{name}.py")


@functools.cache
def _module(path: pathlib.Path):
    kind, name = path.parent.name, path.stem
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"qdbench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
