"""``BENCHMARK.json`` and the files it names.

A cell in ``BENCHMARK.json`` is only names. What belongs to one
configuration, one traffic mix or one metric sits in a file of its own
under one of the benchmark's ``paths``:

    <path>/configs/<config>.json    (or the ``file`` the entry gives)
    <path>/traffic/<traffic>.json
    <path>/metrics/<metric>.json    {"reader": ..., "args": {...}}
    perfbench/readers/<reader>.py   read(ctx, **args) -> number or None
"""

from __future__ import annotations

import json
import os


class Manifest:
    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)

    def _find(self, kind: str, name: str) -> dict:
        for path in self.bench["paths"]:
            file = os.path.join(self.root, path, kind, f"{name}.json")
            if os.path.exists(file):
                with open(file) as f:
                    return json.load(f)
        raise FileNotFoundError(
            f"no {kind}/{name}.json under {self.bench['paths']}"
        )

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        entry = next(c for c in self.bench["configs"]
                     if c["name"] == w["config"])
        with open(os.path.join(self.root, entry["file"])) as f:
            config = json.load(f)
        return {
            "name": name, "chips": w["chips"], "config": config,
            "traffic": self._find("traffic", w["traffic"]),
        }

    def metrics(self, cell_name: str, group: str) -> list:
        """The ``group`` (``end_to_end`` or ``per_layer``) metrics that
        cell reports, each with its reader's name and arguments."""
        out = []
        for m in self.bench[group]:
            if "workloads" in m and cell_name not in m["workloads"]:
                continue
            spec = self._find("metrics", m["name"])
            out.append({**m, "reader": spec["reader"],
                        "args": spec.get("args", {})})
        return out
