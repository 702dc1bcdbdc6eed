"""Loading ``BENCHMARK.json`` and every file it names, and the self-check.

Everything that belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own, found by the name in the manifest:

- ``configs/<config>.json`` (+ the reference its ``reference`` key names)
- ``traffic/<traffic>.json`` -> ``generators/<kind>.py``
- ``layer_metrics/<metric>.json`` -> ``readers/<reader>.py``

so a later PR adds files and entries and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def load_module(path: Path, name: Optional[str] = None):
    """Import a file by path (names with dots and dashes are fine)."""
    if not path.exists():
        raise ManifestError(f"missing file {path}")
    mod_name = name or "bench_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path: Path) -> Dict[str, Any]:
    if not path.exists():
        raise ManifestError(f"missing file {path}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


class Manifest:
    def __init__(self, root: Path) -> None:
        self.root = Path(root)
        self.data = read_json(self.root / "BENCHMARK.json")
        self.bench = self.root / self.data["paths"][0]

    # -- files by name ----------------------------------------------------------
    def config_file(self, name: str) -> Path:
        for c in self.data["configs"]:
            if c["name"] == name:
                return self.root / c["file"]
        raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic_file(self, name: str) -> Path:
        return self.bench / "traffic" / f"{name}.json"

    def metric_file(self, name: str) -> Path:
        return self.bench / "layer_metrics" / f"{name}.json"

    def generator_file(self, kind: str) -> Path:
        return self.bench / "generators" / f"{kind}.py"

    def reader_file(self, reader: str) -> Path:
        return self.bench / "readers" / f"{reader}.py"

    def metrics_of(self, group: str, cell: str) -> List[Dict[str, Any]]:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
        return [
            m for m in self.data[group]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def cell(self, name: str) -> Cell:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return Cell(
                    name=name,
                    chips=int(w["chips"]),
                    config=read_json(self.config_file(w["config"])),
                    traffic=read_json(self.traffic_file(w["traffic"])),
                    end_to_end=self.metrics_of("end_to_end", name),
                    per_layer=self.metrics_of("per_layer", name),
                )
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json")

    # -- the self-check ---------------------------------------------------------
    def check(self) -> None:
        """Raise ``ManifestError`` on the faults that refused earlier PRs."""
        d = self.data
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [x["name"] for x in d[group]]
            for n in names:
                if not NAME_RE.match(n):
                    raise ManifestError(f"{group}: name {n!r} has a character outside the allowed set")
            if len(set(names)) != len(names):
                raise ManifestError(f"{group}: a name appears twice")
        cells = {w["name"]: w for w in d["workloads"]}
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if "setup_s" not in e2e:
            raise ManifestError("end_to_end lacks setup_s")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                raise ManifestError(f"metric {m['name']}: unit {m['unit']!r} is not 1 to 16 allowed characters")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"metric {m['name']}: better is {m['better']!r}")
            if m["source"] not in SOURCES:
                raise ManifestError(f"metric {m['name']}: source {m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    raise ManifestError(f"metric {m['name']} lists an unknown workload {w!r}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"end-to-end metric {m['name']} has source {m['source']!r}")
            if not 0 < m["bound"] <= 0.1:
                raise ManifestError(f"end-to-end metric {m['name']}: bound {m['bound']}")

        def reports(cell: str, metric: str) -> bool:
            m = e2e[metric]
            return "workloads" not in m or cell in m["workloads"]

        for m in d["per_layer"]:
            moved = m["moves"]
            if moved not in e2e:
                raise ManifestError(f"per_layer metric {m['name']} moves {moved!r}, which is no end-to-end metric")
            for w in m.get("workloads") or list(cells):
                if not reports(w, moved):
                    raise ManifestError(
                        f"per_layer metric {m['name']} is reported on workload {w}, "
                        f"where {moved}, which it should move, is not")
        known = {c["name"] for c in d["configs"]}
        for w in d["workloads"]:
            if w["config"] not in known:
                raise ManifestError(f"workload {w['name']}: unknown configuration {w['config']!r}")
        used = {w["config"] for w in d["workloads"]}
        for c in d["configs"]:
            if c["name"] not in used:
                raise ManifestError(f"configuration {c['name']} has no cell")
            for k in c["reduced"]:
                if not NAME_RE.match(k):
                    raise ManifestError(f"configuration {c['name']}: reduced key {k!r}")
        pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
        if len(set(pairs)) != len(pairs):
            raise ManifestError("a pair of configuration and traffic appears twice")
        four = sum(1 for w in d["workloads"] if int(w["chips"]) == 4)
        if four > max(1, len(cells) // 4):
            raise ManifestError(f"{four} of {len(cells)} cells ask for 4 chips: more than a quarter")
        for w in d["workloads"]:
            if int(w["chips"]) not in (1, 4):
                raise ManifestError(f"workload {w['name']}: chips {w['chips']}")
            cell = self.cell(w["name"])
            ref = cell.config.get("reference")
            if not ref or not (self.root / ref).exists():
                raise ManifestError(f"configuration {w['config']}: no plain reference beside it")
            if not self.generator_file(cell.traffic["kind"]).exists():
                raise ManifestError(f"traffic {w['traffic']}: no generator {cell.traffic['kind']!r}")
            if len(cell.end_to_end) < 2:
                raise ManifestError(f"workload {w['name']} reports no end-to-end metric besides setup_s")
            if not cell.per_layer:
                raise ManifestError(f"workload {w['name']} reports no per-layer metric")
            for m in cell.per_layer:
                spec = read_json(self.metric_file(m["name"]))
                if not self.reader_file(spec["reader"]).exists():
                    raise ManifestError(f"per_layer metric {m['name']}: no reader {spec['reader']!r}")
                for k in ("layer", "unit", "moves"):
                    if spec[k] != m[k]:
                        raise ManifestError(f"per_layer metric {m['name']}: {k} differs between its file and the manifest")
