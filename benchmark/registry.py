"""Finds every piece of the benchmark by the name BENCHMARK.json gives it.

A configuration, a traffic mix, a cell, a layer metric, a reducer, a
runner, a family adapter and a reference are each one file under
``benchmark/``; nothing here lists them. A later PR adds files and
entries and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

REPO = Path(__file__).resolve().parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class BenchmarkError(Exception):
    """The benchmark's own files are missing or inconsistent."""


def _named(name: str) -> str:
    if not NAME.match(name):
        raise BenchmarkError(f"not a permitted name: {name!r}")
    return name


class Registry:
    """``root`` is a checkout: it holds BENCHMARK.json and benchmark/."""

    def __init__(self, root: Path = REPO) -> None:
        self.root = Path(root)
        self.dir = self.root / "benchmark"
        self.spec = self._json(self.root / "BENCHMARK.json")

    @staticmethod
    def _json(path: Path) -> Dict[str, Any]:
        try:
            with open(path, encoding="utf-8") as fh:
                return json.load(fh)
        except FileNotFoundError as err:
            raise BenchmarkError(f"missing benchmark file: {path}") from err

    def _entry(self, section: str, name: str) -> Dict[str, Any]:
        for entry in self.spec[section]:
            if entry["name"] == name:
                return entry
        known = [e["name"] for e in self.spec[section]]
        raise BenchmarkError(f"BENCHMARK.json has no {section} entry {name!r}; it has {known}")

    def data(self, kind: str, name: str) -> Dict[str, Any]:
        """The data file ``benchmark/<kind>/<name>.json``."""
        return self._json(self.dir / kind / f"{_named(name)}.json")

    def workload(self, name: str) -> Dict[str, Any]:
        """A cell: its BENCHMARK.json entry over its own file's keys."""
        return {**self.data("workloads", name), **self._entry("workloads", name)}

    def config(self, name: str) -> Dict[str, Any]:
        entry = self._entry("configs", name)
        return {**self._json(self.root / entry["file"]), "name": name}

    def traffic(self, name: str) -> Dict[str, Any]:
        return self.data("traffic", name)

    def metrics(self, section: str, cell: str) -> List[Dict[str, Any]]:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
        return [m for m in self.spec[section] if cell in m.get("workloads", [cell])]

    def module(self, kind: str, name: str) -> ModuleType:
        """The code file ``benchmark/<kind>/<name>.py``, loaded from this checkout."""
        path = self.dir / kind / f"{_named(name)}.py"
        if not path.is_file():
            raise BenchmarkError(f"missing benchmark file: {path}")
        mod_name = f"benchmark.{kind}.{name}".replace("-", "_")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = module
        spec.loader.exec_module(module)
        return module

    def peaks(self, device_kind: str) -> Dict[str, float]:
        table = self._json(self.dir / "peaks.json")
        if device_kind not in table or device_kind == "source":
            raise BenchmarkError(
                f"no peaks for device kind {device_kind!r} in benchmark/peaks.json; "
                "add a row with its source, never a default"
            )
        return table[device_kind]
