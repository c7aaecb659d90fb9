"""BENCHMARK.json and the files it names.  Whatever belongs to one
configuration, one traffic mix, one cell or one per-layer metric is a
file of its own, found by its name; adding one never edits a file that
is already there.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import List, Optional


class Manifest:
    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)

    def _json(self, *parts: str) -> dict:
        with open(os.path.join(self.root, *parts)) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        """A cell is known by its file; BENCHMARK.json's `workloads`
        says which cells the driver checks."""
        return self._json("benchmarks", "cells", f"{name}.json")

    def config(self, name: str) -> dict:
        return self._json("benchmarks", "configs", f"{name}.json")

    def mix(self, name: str) -> dict:
        return self._json("benchmarks", "mixes", f"{name}.json")

    def layer_metric(self, name: str) -> dict:
        return self._json("benchmarks", "layer_metrics", f"{name}.json")

    def metrics_of(self, section: str, cell: str) -> List[dict]:
        """The metrics of `end_to_end` or `per_layer` that this cell
        reports: those that list it, and those that list no cell."""
        return [
            m for m in self.doc[section]
            if "workloads" not in m or cell in m["workloads"]
        ]


def read_metric(spec: dict, ctx: dict) -> Optional[float]:
    """Run the reader a layer metric's file names.  A reader that finds
    nothing to read returns None and the metric is left out."""
    reader = importlib.import_module(f"readers.{spec['reader']}")
    return reader.read(spec.get("args", {}), ctx)
