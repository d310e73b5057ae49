"""BENCHMARK.json and the files it names.

The manifest is the only place a cell, a configuration, a traffic mix or a
metric is named. Everything that belongs to one of them sits in a file of
its own that :class:`Parts` finds by that name: a later PR adds files and
entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import Any, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))

_KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


class ManifestError(ValueError):
    pass


def _line(text: Any, what: str) -> None:
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        raise ManifestError(f"{what}: 1 to 200 characters on one line, no tab")


def _name(text: Any, what: str) -> None:
    if not (isinstance(text, str) and NAME.match(text)):
        raise ManifestError(f"{what}: {text!r} is not a name")


def _entries(doc: dict, key: str, lo: int, hi: int) -> List[dict]:
    rows = doc.get(key)
    if not (isinstance(rows, list) and lo <= len(rows) <= hi):
        raise ManifestError(f"{key}: {lo} to {hi} entries")
    allowed = _KEYS[key] | ({"workloads"} if key in ("end_to_end", "per_layer") else set())
    names = set()
    for row in rows:
        if not isinstance(row, dict):
            raise ManifestError(f"{key}: every entry is an object")
        missing, extra = _KEYS[key] - row.keys(), row.keys() - allowed
        if missing or extra:
            raise ManifestError(
                f"{key} {row.get('name')!r}: missing {sorted(missing)}, "
                f"not allowed {sorted(extra)}"
            )
        _name(row["name"], f"{key} name")
        if row["name"] in names:
            raise ManifestError(f"{key}: {row['name']!r} appears twice")
        names.add(row["name"])
    return rows


def validate(doc: dict) -> None:
    """Refuse a manifest outside the contract's limits (the part of them a
    file can be held to without running anything)."""
    if set(doc) != _KEYS["top"]:
        raise ManifestError(f"top-level keys must be exactly {sorted(_KEYS['top'])}")
    if not (isinstance(doc["command"], list) and 1 <= len(doc["command"]) <= 32):
        raise ManifestError("command: a list of 1 to 32 strings")
    for word in doc["command"]:
        _line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise ManifestError(f"command: {word!r} leads out of the repo")
    paths = doc["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH.match(p) for p in paths)):
        raise ManifestError("paths: 1 to 16 relative directories")
    if not (isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51):
        raise ManifestError("run_seconds: a whole number from 1 to 51")

    configs = _entries(doc, "configs", 1, 24)
    files = set()
    for c in configs:
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            raise ManifestError(f"config {c['name']}: file lies outside paths")
        if c["file"] in files:
            raise ManifestError(f"config file {c['file']} is used twice")
        files.add(c["file"])
        if not (isinstance(c["reduced"], list) and len(c["reduced"]) <= 16):
            raise ManifestError(f"config {c['name']}: reduced has at most 16 keys")
        for key in c["reduced"]:
            _name(key, "reduced key")

    cells = _entries(doc, "workloads", 1, 24)
    pairs = set()
    for w in cells:
        _name(w["config"], "workload config")
        _name(w["traffic"], "workload traffic")
        _line(w["why"], "workload why")
        if w["config"] not in {c["name"] for c in configs}:
            raise ManifestError(f"cell {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"cell {w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"cell {w['name']}: config and traffic appear twice")
        pairs.add((w["config"], w["traffic"]))
    unused = {c["name"] for c in configs} - {w["config"] for w in cells}
    if unused:
        raise ManifestError(f"configs used by no cell: {sorted(unused)}")
    four = sum(w["chips"] == 4 for w in cells)
    if four > max(1, len(cells) // 4):
        raise ManifestError("at most a quarter of the cells (and always one) may take four chips")

    cell_names = {w["name"] for w in cells}
    e2e = _entries(doc, "end_to_end", 1, 16)
    layer = _entries(doc, "per_layer", 1, 128)
    for m in e2e + layer:
        if not UNIT.match(str(m["unit"])):
            raise ManifestError(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise ManifestError(f"metric {m['name']}: better is lower or higher")
        if m["source"] not in SOURCES:
            raise ManifestError(f"metric {m['name']}: source {m['source']!r}")
        for w in m.get("workloads", []):
            if w not in cell_names:
                raise ManifestError(f"metric {m['name']}: unknown cell {w}")
    if len({m["name"] for m in e2e + layer}) != len(e2e) + len(layer):
        raise ManifestError("two metrics share a name")
    for m in e2e:
        if m["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"{m['name']}: an end-to-end metric is taken by the benchmark itself")
        if not (isinstance(m["bound"], (int, float)) and 0 < m["bound"] <= 0.1):
            raise ManifestError(f"{m['name']}: bound is a share in (0, 0.1]")
    if "setup_s" not in {m["name"] for m in e2e}:
        raise ManifestError("end_to_end must hold setup_s")
    by_name = {m["name"]: m for m in e2e}
    for m in layer:
        _line(m["layer"], "layer")
        if m["moves"] not in by_name or m["moves"] == "setup_s":
            raise ManifestError(f"{m['name']}: moves names no end-to-end metric")
        moved = by_name[m["moves"]]
        moved_cells = set(moved.get("workloads", cell_names))
        if not set(m.get("workloads", cell_names)) <= moved_cells:
            raise ManifestError(
                f"{m['name']}: listed in a cell that does not report {m['moves']}"
            )
    for w in cells:
        mine = [m for m in e2e if w["name"] in m.get("workloads", cell_names)]
        if len([m for m in mine if m["name"] != "setup_s"]) < 1:
            raise ManifestError(f"cell {w['name']}: no end-to-end metric beside setup_s")
        if not any(w["name"] in m.get("workloads", cell_names) for m in layer):
            raise ManifestError(f"cell {w['name']}: no per-layer metric")


class Parts:
    """Finds the files of a manifest by name: first under the manifest's own
    ``paths``, then beside the harness, so that a manifest elsewhere (the
    tests' tiny one) can bring parts of its own and use the harness's."""

    def __init__(self, root: str, doc: dict):
        self.root = os.path.abspath(root)
        self.doc = doc
        self.dirs = [os.path.join(self.root, p) for p in doc["paths"]]
        if HARNESS_DIR not in self.dirs:
            self.dirs.append(HARNESS_DIR)

    def _find(self, sub: str, name: str, exts) -> str:
        if not NAME.match(name):
            raise ManifestError(f"{name!r} is not a name")
        for d in self.dirs:
            for ext in exts:
                path = os.path.join(d, sub, name + ext)
                if os.path.isfile(path):
                    return path
        raise ManifestError(f"no {sub}/{name}{'|'.join(exts)} under {self.dirs}")

    def data(self, sub: str, name: str) -> dict:
        with open(self._find(sub, name, (".json",))) as f:
            return json.load(f)

    def table(self, name: str) -> dict:
        """A table that lies at the top of a directory (``peaks.json``)."""
        return self.data("", name)

    def module(self, sub: str, name: str):
        path = self._find(sub, name, (".py",))
        modname = "chipbench_parts." + re.sub(r"\W", "_", os.path.relpath(path, "/"))
        if modname in sys.modules:
            return sys.modules[modname]
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        return mod

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(f"no cell named {name!r} in the manifest")

    def config(self, cell: dict) -> dict:
        entry = next(c for c in self.doc["configs"] if c["name"] == cell["config"])
        with open(os.path.join(self.root, entry["file"])) as f:
            return json.load(f)

    def metrics(self, section: str, cell: dict) -> List[dict]:
        """The manifest's metrics of ``section`` that this cell reports."""
        return [
            m for m in self.doc[section]
            if cell["name"] in m.get("workloads", [cell["name"]])
        ]


def load(root: str = ".", *, check: bool = True) -> Parts:
    path = os.path.join(root, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        raise ManifestError("BENCHMARK.json is over 64 KiB")
    with open(path) as f:
        doc = json.load(f)
    if check:
        validate(doc)
    return Parts(root, doc)
