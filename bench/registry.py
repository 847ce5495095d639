"""Finds a cell's parts by name.

``BENCHMARK.json`` names the cells; each part of a cell is a file of its
own, found by name under each search directory (``bench/`` first):

- ``configs/<config>.json``   a model configuration
- ``families/<family>.py``    everything that depends on a configuration's
  ``model.family``, as the functions of ``FAMILY_API``:

  - ``dims(name, conf)``: the sizes, a frozen, hashable object with at
    least ``name``, ``vocab``, ``d_model``, ``weight_bits``, ``slots`` and
    ``max_len`` (the fields generic code reads);
  - ``model_config(d)``: the ``repro.models.config.ModelConfig`` the
    engine is built with;
  - ``make_weights(d, seed)``: the packed tree the engine takes, made from
    the seed (the comparison makes it again for the reference);
  - ``decode_call(d, keys)`` -> (flops, bytes) and ``prefill_flops(d,
    tokens, key_sum)``: the counts the roofline and MFU readers use;
  - ``warm_prefill(engine, mix, d)``: compile and run once every prefill
    program the mix can reach, called as the engine calls it for this
    family (row batch, bucket or exact length, extra arguments), after a
    few requests have gone through a scheduler.
- ``references/<name>.py``   the plain reference a configuration names
  under ``reference`` (``score``; see ``bench/correct.py``)
- ``traffic/<mix>.json``      a traffic mix (see ``bench/traffic.py``)
- ``metrics/<metric>.py``     a metric reader: ``read(window)`` gives the
  value, or None where it finds nothing to read
- ``peaks.json``              the device peaks, keyed by ``device_kind``

A new configuration, family, reference, mix or metric is a new file (and,
for a cell or a metric, a new entry in ``BENCHMARK.json``); nothing here
changes.  Every module part (family, reference, metric reader) is loaded
from its file once per process, whichever registry asks for it; code that
needs a part, tests included, gets it from a registry.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional, Sequence

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
# what a family module defines; generic code reaches the model only here
FAMILY_API = ("dims", "model_config", "make_weights", "decode_call",
              "prefill_flops", "warm_prefill")
# every module part loaded, by its file's path
_LOADED: Dict[Path, ModuleType] = {}


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

    def _module(self, sub: str, name: str) -> ModuleType:
        path = self._find(sub, name, ".py").resolve()
        if path not in _LOADED:
            mod_name = (f"bench_{sub}_"
                        f"{name.replace('.', '_').replace('-', '_')}")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            # dataclasses look up the module of a class they process
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            _LOADED[path] = mod
        return _LOADED[path]

    def reader(self, name: str):
        return self._module("metrics", name).read

    def reference(self, name: str):
        return self._module("references", name)

    def family(self, name: str):
        """The module of the family ``name`` (a configuration's
        ``model.family``); one that lacks a function of ``FAMILY_API`` is
        an error."""
        mod = self._module("families", name)
        missing = [f for f in FAMILY_API
                   if not callable(getattr(mod, f, None))]
        if missing:
            raise AttributeError(
                f"family {name!r} ({mod.__file__}) defines no "
                f"{', '.join(missing)}")
        return mod

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
