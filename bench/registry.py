"""Finds a cell's parts by name.

``BENCHMARK.json`` names the cells; each part of a cell is a file of its
own, found by name under each search directory (``bench/`` first):

- ``configs/<config>.json``   a model configuration (see ``bench/model.py``)
- ``traffic/<mix>.json``      a traffic mix (see ``bench/traffic.py``)
- ``metrics/<metric>.py``     a metric reader: ``read(window)`` gives the
  value, or None where it finds nothing to read
- ``peaks.json``              the device peaks, keyed by ``device_kind``

A new configuration, mix or metric is a new file and a new entry in
``BENCHMARK.json``; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


class Registry:
    def __init__(self, dirs: Sequence[Path] = (BENCH,),
                 benchmark: Optional[Path] = None):
        self.dirs = [Path(p) for p in dirs]
        path = benchmark or ROOT / "BENCHMARK.json"
        self.doc = json.loads(Path(path).read_text())

    def _find(self, sub: str, name: str, ext: str) -> Path:
        for d in self.dirs:
            p = d / sub / f"{name}{ext}"
            if p.is_file():
                return p
        raise KeyError(f"no {sub}/{name}{ext} under "
                       f"{[str(d) for d in self.dirs]}")

    def _names(self, sub: str, ext: str) -> List[str]:
        names = set()
        for d in self.dirs:
            names |= {p.name[:-len(ext)] for p in (d / sub).glob(f"*{ext}")
                      if not p.name.startswith("_")}
        return sorted(names)

    # -- listings ---------------------------------------------------------------
    def configs(self) -> List[str]:
        return self._names("configs", ".json")

    def mixes(self) -> List[str]:
        return self._names("traffic", ".json")

    def metric_readers(self) -> List[str]:
        return self._names("metrics", ".py")

    # -- parts --------------------------------------------------------------------
    def config(self, name: str) -> Dict:
        return json.loads(self._find("configs", name, ".json").read_text())

    def mix(self, name: str) -> Dict:
        return json.loads(self._find("traffic", name, ".json").read_text())

    def reader(self, name: str):
        path = self._find("metrics", name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def peaks(self, device_kind: str) -> Dict:
        for d in self.dirs:
            p = d / "peaks.json"
            if p.is_file():
                table = json.loads(p.read_text())["devices"]
                if device_kind in table:
                    return table[device_kind]
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table")

    # -- BENCHMARK.json -------------------------------------------------------------
    def workload(self, name: str) -> Dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def metrics_for(self, workload: str, kind: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or workload in m["workloads"]]
