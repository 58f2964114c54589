"""``BENCHMARK.json`` and the data files it names.

A cell is found by its name alone: ``workloads/<cell>.json`` names its
configuration and traffic mix, which live in ``configs/<config>.json``
and ``traffic/<mix>.json``; a per-layer metric ``<reader>[.<tag>]`` is
read by ``layer_metrics/<reader>.py``. Adding any of them adds files and
``BENCHMARK.json`` entries and edits nothing here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from typing import Any

BENCH_DIR = "benchmarks"
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class ManifestError(ValueError):
    pass


def _load(path: str) -> Any:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the three files it resolves to."""

    name: str
    chips: int
    entry: dict          # the BENCHMARK.json workloads entry
    workload: dict       # workloads/<cell>.json
    config: dict         # configs/<config>.json
    traffic: dict        # traffic/<mix>.json
    end_to_end: tuple    # BENCHMARK.json metric entries that apply here
    per_layer: tuple


def applies(metric: dict, cell: str) -> bool:
    """A metric without ``workloads`` exists in every cell."""
    return "workloads" not in metric or cell in metric["workloads"]


class Manifest:
    def __init__(self, root: str):
        self.root = root
        self.bench = os.path.join(root, BENCH_DIR)
        self.data = _load(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> Cell:
        entries = {w["name"]: w for w in self.data["workloads"]}
        if name not in entries:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json "
                f"(have: {', '.join(sorted(entries))})")
        entry = entries[name]
        workload = _load(os.path.join(self.bench, "workloads", f"{name}.json"))
        for key in ("config", "traffic", "chips"):
            if workload.get(key) != entry[key]:
                raise ManifestError(
                    f"workloads/{name}.json says {key}={workload.get(key)!r}, "
                    f"BENCHMARK.json says {entry[key]!r}")
        cfg_entry = {c["name"]: c for c in self.data["configs"]}[entry["config"]]
        config = _load(os.path.join(self.root, cfg_entry["file"]))
        traffic = _load(os.path.join(
            self.bench, "traffic", f"{entry['traffic']}.json"))
        return Cell(
            name=name, chips=int(entry["chips"]), entry=entry,
            workload=workload, config=config, traffic=traffic,
            end_to_end=tuple(m for m in self.data["end_to_end"]
                             if applies(m, name)),
            per_layer=tuple(m for m in self.data["per_layer"]
                            if applies(m, name)),
        )


def reader_name(metric_name: str) -> str:
    """``device_idle_pct.images`` is read by ``device_idle_pct.py``: the
    part after the first dot only tells two entries of one reader apart
    (one per end-to-end metric it moves)."""
    return metric_name.split(".", 1)[0]


def load_module(root: str, package: str, name: str):
    """``<root>/benchmarks/<package>/<name>.py`` as a module, loaded from
    that file: a checkout's benchmark reads its own files, whatever else
    of the same name is importable."""
    if not NAME_RE.match(name) or "." in name:
        raise ManifestError(f"bad module name {name!r}")
    path = os.path.join(root, BENCH_DIR, *package.split("/"), f"{name}.py")
    key = f"_bench_{package.replace('/', '_')}_{name}_{abs(hash(path)):x}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None or not os.path.isfile(path):
        raise ManifestError(f"no {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[key] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[key]
        raise
    return module


def load_reader(root: str, metric_name: str):
    return load_module(root, "layer_metrics", reader_name(metric_name))


def load_generator(root: str, kind: str):
    return load_module(root, "traffic/generators", kind)


def load_family(root: str, package: str, family: str):
    """``flops/<family>.py`` or ``reference/<family>.py``."""
    return load_module(root, package, family)


# --------------------------------------------------------------- checker --
def check(root: str) -> list[str]:
    """Every way this tree breaks the benchmark's contract that can be
    seen without running anything. Empty when it holds."""
    errs: list[str] = []
    try:
        man = Manifest(root)
    except (OSError, json.JSONDecodeError) as e:
        return [f"BENCHMARK.json unreadable: {e}"]
    d = man.data
    if set(d) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(d)} != {sorted(TOP_KEYS)}")
        return errs
    if os.path.getsize(os.path.join(root, "BENCHMARK.json")) > 64 * 1024:
        errs.append("BENCHMARK.json over 64 KiB")

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            errs.append(f"{what}: bad name {n!r}")

    def line_ok(s, what):
        if not (isinstance(s, str) and 1 <= len(s) <= 200
                and "\n" not in s and "\t" not in s):
            errs.append(f"{what}: not one line of 1-200 characters")

    def keys_ok(entry, required, optional, what):
        extra = set(entry) - set(required) - set(optional)
        missing = set(required) - set(entry)
        if extra or missing:
            errs.append(f"{what}: extra keys {sorted(extra)}, "
                        f"missing {sorted(missing)}")

    for w in d["command"]:
        line_ok(w, "command word")
    if not 1 <= len(d["command"]) <= 32:
        errs.append("command has more than 32 words")
    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        errs.append(f"run_seconds {d['run_seconds']!r} outside 1..51")
    for p in d["paths"]:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/") \
                or ".." in p.split("/"):
            errs.append(f"bad path {p!r}")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in d["paths"])

    cfg_names, files = set(), set()
    for c in d["configs"]:
        keys_ok(c, ("name", "source", "file", "reduced", "why"), (), "config")
        name_ok(c.get("name"), "config")
        line_ok(c.get("source"), f"config {c.get('name')} source")
        line_ok(c.get("why"), f"config {c.get('name')} why")
        for k in c.get("reduced", []):
            name_ok(k, f"config {c.get('name')} reduced")
        if c["name"] in cfg_names:
            errs.append(f"config {c['name']} twice")
        cfg_names.add(c["name"])
        if c["file"] in files or not under_paths(c["file"]):
            errs.append(f"config file {c['file']} shared or outside paths")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(root, c["file"])):
            errs.append(f"config file {c['file']} missing")

    e2e = {m["name"]: m for m in d["end_to_end"]}
    cells, pairs = set(), set()
    for w in d["workloads"]:
        keys_ok(w, ("name", "config", "traffic", "chips", "why"), (),
                "workload")
        for k in ("name", "config", "traffic"):
            name_ok(w.get(k), f"workload {k}")
        line_ok(w.get("why"), f"workload {w.get('name')} why")
        if w["name"] in cells:
            errs.append(f"cell {w['name']} twice")
        cells.add(w["name"])
        if (w["config"], w["traffic"]) in pairs:
            errs.append(f"pair {w['config']}/{w['traffic']} twice")
        pairs.add((w["config"], w["traffic"]))
        if w["config"] not in cfg_names:
            errs.append(f"cell {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            errs.append(f"cell {w['name']}: chips {w['chips']!r}")
        try:
            man.cell(w["name"])
        except (OSError, KeyError, ManifestError, json.JSONDecodeError) as e:
            errs.append(f"cell {w['name']}: files not found or disagree: {e}")
    if not 2 <= len(cells) <= 24:
        errs.append(f"{len(cells)} cells, need 2..24")
    four = sum(1 for w in d["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        errs.append(f"{four} four-chip cells of {len(cells)}")
    for c in cfg_names - {w["config"] for w in d["workloads"]}:
        errs.append(f"config {c} used by no cell")

    seen = set()
    for kind, required in (
            ("end_to_end", ("name", "unit", "better", "bound", "source")),
            ("per_layer", ("name", "unit", "better", "source", "layer",
                           "moves"))):
        for m in d[kind]:
            what = f"{kind} {m.get('name')}"
            keys_ok(m, required, ("workloads",), what)
            name_ok(m.get("name"), what)
            if m["name"] in seen:
                errs.append(f"metric {m['name']} twice")
            seen.add(m["name"])
            if not UNIT_RE.match(str(m.get("unit", ""))):
                errs.append(f"{what}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                errs.append(f"{what}: better={m.get('better')!r}")
            if m.get("source") not in SOURCES:
                errs.append(f"{what}: source={m.get('source')!r}")
            for wl in m.get("workloads", []):
                if wl not in cells:
                    errs.append(f"{what}: unknown workload {wl}")
            if kind == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    errs.append(f"{what}: source {m['source']}")
                if not (isinstance(m["bound"], (int, float))
                        and 0.01 <= m["bound"] <= 0.1):
                    errs.append(f"{what}: bound {m['bound']!r}")
            else:
                line_ok(m.get("layer"), f"{what} layer")
                if m.get("moves") not in e2e:
                    errs.append(f"{what}: moves unknown {m.get('moves')!r}")
                    continue
                where = set(m.get("workloads", cells))
                moved = set(e2e[m["moves"]].get("workloads", cells))
                if not where <= moved:
                    errs.append(f"{what}: reported in {sorted(where - moved)}"
                                f" where {m['moves']} is not")
                path = os.path.join(man.bench, "layer_metrics",
                                    reader_name(m["name"]) + ".py")
                if not os.path.isfile(path):
                    errs.append(f"{what}: no reader {path}")
    if "setup_s" not in e2e:
        errs.append("no setup_s")
    for c in cells:
        mine = [m for m in d["end_to_end"] if applies(m, c)]
        if len([m for m in mine if m["name"] != "setup_s"]) < 1 \
                or not any(m["name"] == "setup_s" for m in mine):
            errs.append(f"cell {c}: needs setup_s and one more end-to-end")
        if not any(applies(m, c) for m in d["per_layer"]):
            errs.append(f"cell {c}: no per-layer metric")
    return errs
