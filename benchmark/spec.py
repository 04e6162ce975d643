"""What a run is made of, found by NAME: the cell and its metrics from
`BENCHMARK.json`, the configuration from its `file`, the traffic mix from
`traffic/<mix>.json`, the limits of `correct` from `limits/<cell>.json`,
each per-layer metric's reader from `readers/<base name>.py`, and the
model family the configuration names from `families/<family>.py` with its
plain reference `reference/<family>.py`. The harness holds no table of
cells, mixes, metrics or families: a later PR adds files and list entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    def __init__(self, name: str, root: str = ROOT, here: str = HERE):
        self.bench = _load(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
        self.here = here
        self._loaded = {}       # a tree's own files, loaded once a cell
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = next(c for c in self.bench["configs"]
                         if c["name"] == self.entry["config"])
        self.config = _load(os.path.join(root, cfg_entry["file"]))
        if "family" not in self.config:
            raise SystemExit(
                f"{cfg_entry['file']} names no \"family\": say which "
                f"benchmark/families/<family>.py and benchmark/reference/"
                f"<family>.py hold its weights, program builder, leaf map, "
                f"counts and plain reference")
        self.mix = _load(os.path.join(here, "traffic",
                                      self.entry["traffic"] + ".json"))
        lim = os.path.join(here, "limits", name + ".json")
        self.limits = _load(lim)["limits"] if os.path.exists(lim) else {}
        self.run_seconds = self.bench["run_seconds"]

    def metrics(self, group: str) -> list:
        """Entries of `end_to_end` or `per_layer` that this cell reports
        (no `workloads` key: every cell)."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def find(self, *parts: str):
        """A file of the benchmark by its place and NAME: the cell's own
        tree first (a test's fixture tree), then the stock files."""
        for here in (self.here, HERE):
            path = os.path.join(here, *parts)
            if os.path.exists(path):
                return path
        return None

    def module(self, *parts: str):
        """The code that belongs to one name, loaded from its file:
        `readers/<base>.py` for a per-layer metric, `drive_<kind>.py` for
        a kind of traffic. One path for stock and added files alike."""
        path = self.find(*parts)
        if path is None:
            raise SystemExit(f"benchmark/{'/'.join(parts)} is not there: "
                             f"add the file, the harness finds it by name")
        name = "benchmark." + ".".join(parts)[:-len(".py")]
        mod = self._loaded.get(path) or sys.modules.get(name)
        if mod is None or getattr(mod, "__file__", None) != path:
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            if path.startswith(HERE + os.sep):
                sys.modules[name] = mod     # the stock file IS that module
            spec.loader.exec_module(mod)
        self._loaded[path] = mod
        return mod

    @property
    def family(self):
        """`families/<family>.py`: the weights, the program's model and
        its leaves, the counts of operations and bytes."""
        return self.module("families", self.config["family"] + ".py")

    @property
    def reference(self):
        """`reference/<family>.py`: the family's plain reference."""
        return self.module("reference", self.config["family"] + ".py")

    def driver(self):
        """`drive_<kind>.py::run` for the mix's `kind`."""
        return self.module(f"drive_{self.mix['kind']}.py").run

    def reader(self, metric_name: str):
        """`readers/<base>.py::read`, base = the name up to its first dot
        suffix (`decode_step_device_ms.tpot` -> decode_step_device_ms)."""
        return self.module("readers", metric_name.split(".")[0] + ".py").read

    def selector(self, base: str) -> dict:
        """Data beside a reader: `readers/<base>.json` (name patterns)."""
        path = self.find("readers", base + ".json")
        return _load(path) if path else {}


def peaks(kind: str, here: str = HERE) -> dict:
    table = _load(os.path.join(here, "peaks.json"))
    if kind not in table or kind == "source":
        raise SystemExit(f"device_kind {kind!r} is not in benchmark/"
                         f"peaks.json: add its published peaks, with the "
                         f"source, before measuring on it")
    return table[kind]
