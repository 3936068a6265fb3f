"""Finding things by name. BENCHMARK.json is the list of cells and
metrics; whatever belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under benchmark/, found by the name
BENCHMARK.json gives it. A later PR adds entries and files and edits none
that is there.

  configuration  <its `file` in BENCHMARK.json>
  traffic mix    benchmark/traffic/<traffic>.<config>.json
  per-layer      benchmark/layer_metrics/<name>.json, or, for a name split
                 by the end-to-end metric it moves (`x.backlog`), the file
                 of the name before its last dot (`x.json`)
  reader         benchmark/readers/<kind>.py, one function `read(ctx, **args)`
  reference rule benchmark/reference_rules/<name>.py, for each name a
                 configuration lists under `"reference_rules"`: one more
                 filter of the plain reference (harness/check.py), two
                 functions `why_not(manifest, node_name, cluster)` and
                 `bind(manifest, node_name, cluster)`, nothing of the program

A configuration may also state `"rehearsal_nodes"`: the size of its CPU
rehearsal (tests/benchmark), 64 unless it needs more, 256 at most.
"""

from __future__ import annotations

import importlib.util
import json
import os

from .check import SMALL_CLUSTER_NODES

REHEARSAL_NODES = 64


class Catalog:
    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self.dir = os.path.join(root, "benchmark")

    def cells(self) -> list:
        return [w["name"] for w in self.bench["workloads"]]

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"it has {self.cells()}")

    def _json(self, path: str) -> dict:
        with open(path) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return self._json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, cell: dict) -> dict:
        return self._json(os.path.join(
            self.dir, "traffic", f"{cell['traffic']}.{cell['config']}.json"))

    def metrics(self, section: str, cell_name: str) -> list:
        """The entries of `end_to_end` or `per_layer` that this cell
        reports: those with no `workloads` key, or with the cell in it."""
        return [m for m in self.bench[section]
                if "workloads" not in m or cell_name in m["workloads"]]

    def layer_metric(self, name: str) -> dict:
        """{"reader": kind, "args": {...}} for a per-layer metric."""
        for stem in (name, name.rsplit(".", 1)[0]):
            path = os.path.join(self.dir, "layer_metrics", stem + ".json")
            if os.path.exists(path):
                return self._json(path)
        raise KeyError(f"no benchmark/layer_metrics file for {name!r}")

    def _module(self, folder: str, name: str):
        path = os.path.join(self.dir, folder, name + ".py")
        if not os.path.exists(path):
            raise KeyError(f"no benchmark/{folder}/{name}.py for {name!r}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{folder}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, kind: str):
        return self._module("readers", kind).read

    def reference_rules(self, config: dict) -> list:
        """The modules of the rules `config` names, in its order; none for
        a configuration that names none."""
        return [self._module("reference_rules", name)
                for name in config.get("reference_rules", [])]

    @staticmethod
    def rehearsal_nodes(config: dict) -> int:
        n = int(config.get("rehearsal_nodes", REHEARSAL_NODES))
        # up to there the program's own small-batch host lane is tolerated
        if not 1 <= n <= SMALL_CLUSTER_NODES:
            raise ValueError(f"rehearsal_nodes {n}: a rehearsal has 1 to "
                             f"{SMALL_CLUSTER_NODES} nodes")
        return n

    def read_layer_metrics(self, cell_name: str, ctx: dict) -> dict:
        """name -> {"value", "unit"} for every per-layer metric of the
        cell whose reader found something to read."""
        out = {}
        for m in self.metrics("per_layer", cell_name):
            spec = self.layer_metric(m["name"])
            value = self.reader(spec["reader"])(ctx, **spec.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
