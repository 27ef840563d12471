"""BENCHMARK.json and the files it names, found by name.

A cell resolves to a configuration file, a traffic file, a family module
(`models/<family>.py`), a driver module (`drivers/<mode>.py`) and one
reader per per-layer metric (`metrics/<name>.py`). Each is looked up by
its name in every directory of the manifest's `paths`, in order, so a
later PR adds a cell, a mix, a family or a metric by adding files and
entries, and edits nothing that is here.
"""
import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _module_from(path):
    name = "perfbench_" + re.sub(r"\W", "_", os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """The parsed BENCHMARK.json with `root`, the directory it lies in."""

    def __init__(self, path):
        self.path = os.path.abspath(path)
        self.root = os.path.dirname(self.path)
        with open(self.path) as f:
            self.data = json.load(f)
        self.paths = list(self.data["paths"])
        self.run_seconds = self.data["run_seconds"]
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.cells = {w["name"]: w for w in self.data["workloads"]}
        self.end_to_end = list(self.data["end_to_end"])
        self.per_layer = list(self.data["per_layer"])

    # -- lookup by name ------------------------------------------------------
    def find(self, *parts):
        """First `<path>/<parts...>` that exists over the manifest's paths."""
        for p in self.paths:
            cand = os.path.join(self.root, p, *parts)
            if os.path.exists(cand):
                return cand
        raise ManifestError(
            f"{os.path.join(*parts)} is in none of the paths {self.paths}")

    def cell(self, name):
        if name not in self.cells:
            raise ManifestError(
                f"no workload {name!r}; have {sorted(self.cells)}")
        return self.cells[name]

    def config(self, cell):
        entry = self.configs[cell["config"]]
        with open(os.path.join(self.root, entry["file"])) as f:
            cfg = json.load(f)
        cfg["_name"] = entry["name"]
        return cfg

    def traffic(self, cell):
        for ext in TRAFFIC_EXT:
            try:
                path = self.find("traffic", cell["traffic"] + ext)
            except ManifestError:
                continue
            if ext != ".json":
                raise ManifestError(
                    f"{path}: this harness reads .json traffic files")
            with open(path) as f:
                mix = json.load(f)
            mix["_name"] = cell["traffic"]
            return mix
        raise ManifestError(f"no traffic file for mix {cell['traffic']!r}")

    def family(self, cfg):
        return _module_from(self.find("models", cfg["family"] + ".py"))

    def driver(self, cfg):
        return _module_from(self.find("drivers", cfg["mode"] + ".py"))

    # -- which metrics a cell reports ------------------------------------------
    @staticmethod
    def _applies(metric, cell_name):
        return "workloads" not in metric or cell_name in metric["workloads"]

    def end_to_end_of(self, cell_name):
        return [m for m in self.end_to_end if self._applies(m, cell_name)]

    def per_layer_of(self, cell_name):
        """The per-layer metrics of a cell: those that list it, and those
        without a list whose `moves` the cell reports."""
        e2e = {m["name"] for m in self.end_to_end_of(cell_name)}
        out = []
        for m in self.per_layer:
            if "workloads" in m:
                if cell_name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out

    def reader(self, metric):
        return _module_from(self.find("metrics", metric["name"] + ".py")).read


def validate(m):
    """The contract's shape rules that a test can hold the file to.
    Returns a list of complaints, empty when the manifest is sound."""
    bad = []
    d = m.data
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(d) != want:
        bad.append(f"keys {sorted(d)} != {sorted(want)}")
    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        bad.append("run_seconds must be a whole number in 1..51")
    e2e_names = {x["name"] for x in d["end_to_end"]}
    if "setup_s" not in e2e_names:
        bad.append("no setup_s among end_to_end")
    names = ([c["name"] for c in d["configs"]]
             + [w["name"] for w in d["workloads"]]
             + [x["name"] for x in d["end_to_end"] + d["per_layer"]])
    for n in names + [w["traffic"] for w in d["workloads"]]:
        if not NAME_RE.match(n):
            bad.append(f"name {n!r} has characters outside the contract's")
    for group in (d["configs"], d["workloads"], d["end_to_end"] + d["per_layer"]):
        seen = [x["name"] for x in group]
        if len(seen) != len(set(seen)):
            bad.append(f"duplicate names in {sorted(seen)}")
    for x in d["end_to_end"] + d["per_layer"]:
        if not UNIT_RE.match(x["unit"]):
            bad.append(f"unit {x['unit']!r} of {x['name']}")
        if x["better"] not in ("lower", "higher"):
            bad.append(f"better of {x['name']}")
        if x["source"] not in SOURCES:
            bad.append(f"source of {x['name']}")
    for x in d["end_to_end"]:
        if set(x) - {"name", "unit", "better", "bound", "source", "workloads"}:
            bad.append(f"extra keys on {x['name']}")
        if x["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {x['name']} from {x['source']}")
        if not 0.01 <= x["bound"] <= 0.1:
            bad.append(f"bound of {x['name']}")
    layers = {}
    for x in d["per_layer"]:
        if set(x) - {"name", "unit", "better", "source", "layer", "moves",
                     "workloads"}:
            bad.append(f"extra keys on {x['name']}")
        if x["moves"] not in e2e_names:
            bad.append(f"{x['name']} moves unknown {x['moves']}")
        layers.setdefault(x["layer"].lower(), set()).add(x["layer"])
    for variants in layers.values():
        if len(variants) > 1:
            bad.append(f"one layer spelt {sorted(variants)}")
    pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
    if len(pairs) != len(set(pairs)):
        bad.append("a (config, traffic) pair appears twice")
    used = {w["config"] for w in d["workloads"]}
    for c in d["configs"]:
        if c["name"] not in used:
            bad.append(f"config {c['name']} is used by no cell")
        if not any(c["file"].startswith(p + "/") for p in d["paths"]):
            bad.append(f"{c['file']} is outside paths")
    four = sum(1 for w in d["workloads"] if w["chips"] == 4)
    if four > max(1, len(d["workloads"]) // 4):
        bad.append(f"{four} four-chip cells of {len(d['workloads'])}")
    for w in d["workloads"]:
        if w["chips"] not in (1, 4):
            bad.append(f"chips of {w['name']}")
        if not 1 <= len(w["why"]) <= 200:
            bad.append(f"why of {w['name']} has {len(w['why'])} characters")
        e2e = [x for x in m.end_to_end_of(w["name"])]
        if {x["name"] for x in e2e} == {"setup_s"} or "setup_s" not in \
                {x["name"] for x in e2e}:
            bad.append(f"{w['name']} reports setup_s and nothing else, or "
                       "no setup_s")
        if not m.per_layer_of(w["name"]):
            bad.append(f"{w['name']} has no per-layer metric")
    # a per-layer metric's cells must report the end-to-end metric it moves
    for x in d["per_layer"]:
        cells = x.get("workloads")
        if cells is None:
            continue
        for c in cells:
            if c not in m.cells:
                bad.append(f"{x['name']} lists unknown cell {c}")
            elif x["moves"] not in {y["name"] for y in m.end_to_end_of(c)}:
                bad.append(f"{x['name']} moves {x['moves']}, which {c} "
                           "does not report")
    return bad
