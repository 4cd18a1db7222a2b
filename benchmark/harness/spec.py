"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is ``{name, config, traffic, chips}``.  Its configuration is the
``file`` of the ``configs`` entry; its traffic mix is ``traffic/<traffic>.json``,
whose ``generator`` names ``generators/<generator>.py``; a per-layer metric
``m`` is read by ``layer_metrics/<m>.py``; the configuration file's ``family``
names ``families/<family>.py``, which holds everything about the cell that
depends on the model and names the file of its plain reference beside it.
The directories are looked for under each of ``paths`` in turn, so a later PR
adds a mix, a generator, a metric or a model family by adding files and
entries, and edits nothing.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]          # the configuration file, as run
    traffic: Dict[str, Any]         # the traffic file
    end_to_end: List[Dict[str, Any]]   # metric entries this cell reports
    per_layer: List[Dict[str, Any]]


# What a family file has to define (``families/gpt2.py`` says what each is).
FAMILY_NAMES = ("REFERENCE", "TOLERANCES", "build_model", "vocab_size",
                "forward_logits", "shard_witness", "kernel_expected",
                "train_flops_per_token", "serve_flops_per_token",
                "serve_probe")
REFERENCE_NAMES = ("logits", "token_losses", "tail_logits", "top2")
TOLERANCE_NAMES = ("logit", "min_agreement", "loss", "token_loss")


class Family:
    """``families/<name>.py`` and the reference file it names: attribute
    access falls through to the family's module."""

    def __init__(self, name: str, module, reference):
        self.name, self.module, self.reference = name, module, reference

    def __getattr__(self, attr: str):
        return getattr(self.module, attr)


class Benchmark:
    """``BENCHMARK.json`` at ``root`` and the files under its ``paths``."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        path = os.path.join(self.root, "BENCHMARK.json")
        try:
            with open(path) as f:
                self.doc = json.load(f)
        except OSError as e:
            raise SpecError(f"cannot read {path}: {e}") from e
        self.search_dirs = [os.path.join(self.root, p)
                            for p in self.doc["paths"]]

    # ------------------------------------------------------------ lookup

    def find(self, kind: str, name: str, suffix: str) -> str:
        """Path of ``<kind>/<name><suffix>`` under the first of ``paths``
        that has it."""
        if not NAME_RE.match(name):
            raise SpecError(f"{kind} name {name!r} has characters a name "
                            "may not have")
        for d in self.search_dirs:
            p = os.path.join(d, kind, name + suffix)
            if os.path.isfile(p):
                return p
        raise SpecError(f"no {kind}/{name}{suffix} under {self.doc['paths']}")

    def load_module(self, kind: str, name: str):
        path = self.find(kind, name, ".py")
        mod_name = f"_bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
        spec = importlib.util.spec_from_file_location(mod_name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = module     # dataclasses look their module up
        spec.loader.exec_module(module)
        return module

    def _entry(self, section: str, name: str) -> Dict[str, Any]:
        for e in self.doc[section]:
            if e["name"] == name:
                return e
        raise SpecError(f"no entry {name!r} in {section} of BENCHMARK.json")

    @staticmethod
    def _reported_in(metric: Dict[str, Any], cell_name: str) -> bool:
        cells = metric.get("workloads")
        return cells is None or cell_name in cells

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", name)
        c = self._entry("configs", w["config"])
        with open(os.path.join(self.root, c["file"])) as f:
            config = json.load(f)
        with open(self.find("traffic", w["traffic"], ".json")) as f:
            traffic = json.load(f)
        return Cell(
            name=name, chips=int(w["chips"]), config_name=w["config"],
            traffic_name=w["traffic"], config=config, traffic=traffic,
            end_to_end=[m for m in self.doc["end_to_end"]
                        if self._reported_in(m, name)],
            per_layer=[m for m in self.doc["per_layer"]
                       if self._reported_in(m, name)])

    def generator(self, cell: Cell):
        return self.load_module("generators", cell.traffic["generator"])

    def family(self, cell: Cell) -> Family:
        """The model family the cell's configuration file names."""
        name = cell.config.get("family")
        if not isinstance(name, str):
            raise SpecError(f"the configuration {cell.config_name!r} names "
                            "no \"family\"")
        module = self.load_module("families", name)
        _require(module, FAMILY_NAMES, f"families/{name}.py")
        _require(module.TOLERANCES, TOLERANCE_NAMES,
                 f"TOLERANCES of families/{name}.py")
        reference = self.load_module("families", module.REFERENCE)
        _require(reference, REFERENCE_NAMES,
                 f"families/{module.REFERENCE}.py")
        return Family(name, module, reference)

    def layer_reader(self, metric_name: str):
        """The ``read(record, trace) -> float | None`` of one metric."""
        return self.load_module("layer_metrics", metric_name).read


def _require(holder, names, where: str) -> None:
    has = holder.__contains__ if isinstance(holder, dict) else (
        lambda n: hasattr(holder, n))
    missing = [n for n in names if not has(n)]
    if missing:
        raise SpecError(f"{where} lacks {', '.join(missing)}")


def select(entries: List[Dict[str, Any]], values: Dict[str, Optional[float]]
           ) -> Dict[str, Dict[str, Any]]:
    """The last line's ``metrics``: every listed entry that has a value, as
    measured and unrounded.  A metric with nothing to read is left out."""
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in entries if values.get(m["name"]) is not None}
