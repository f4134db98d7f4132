"""Finds everything by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; those name a builder, a
reference and a driver; a metric name is a module under ``metrics/``.
Nothing is registered in code, so a later PR adds files and entries and
edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_manifest(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold dots
    and dashes, so they are loaded by path, not imported by name)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    mod_name = "benchmarks_%s_%s" % (kind, re.sub(r"[^A-Za-z0-9_]", "_", name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(
        f"no workload {name!r} in BENCHMARK.json; it has "
        f"{[c['name'] for c in manifest['workloads']]}"
    )


def config_of(manifest: dict, cell: dict) -> dict:
    """The cell's configuration file, as it is run."""
    for entry in manifest["configs"]:
        if entry["name"] == cell["config"]:
            with open(os.path.join(ROOT, entry["file"])) as f:
                return json.load(f)
    raise KeyError(f"no config {cell['config']!r} in BENCHMARK.json")


def metrics_of(manifest: dict, cell: dict, section: str) -> list[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports. An entry with a ``workloads`` key lists its cells; a
    per-layer entry without one goes wherever the end-to-end metric it
    moves is reported; an end-to-end entry without one is in every cell."""
    e2e = [
        m for m in manifest["end_to_end"]
        if "workloads" not in m or cell["name"] in m["workloads"]
    ]
    if section == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [
        m for m in manifest["per_layer"]
        if (cell["name"] in m["workloads"] if "workloads" in m
            else m["moves"] in reported)
    ]


def check_manifest(manifest: dict) -> list[str]:
    """What a reader of the contract would refuse, as messages; empty
    when the file is sound. (The driver's own check is the judge: this
    one is for the selftest and for a builder before a chip call.)"""
    bad = []
    names = set()
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        if key not in manifest:
            bad.append(f"missing key {key}")
    if set(manifest) - {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}:
        bad.append("unknown top-level key")
    configs = {c["name"] for c in manifest["configs"]}
    cells = {c["name"] for c in manifest["workloads"]}
    for c in manifest["configs"]:
        if not NAME.match(c["name"]):
            bad.append(f"config name {c['name']!r}")
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']}: keys {sorted(c)}")
        if not os.path.isfile(os.path.join(ROOT, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            bad.append(f"config {c['name']}: used by no cell")
    pairs = set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"cell {w.get('name')}: keys {sorted(w)}")
        for key in ("name", "config", "traffic"):
            if not NAME.match(w[key]):
                bad.append(f"cell {key} {w[key]!r}")
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append(f"cell {w['name']}: why of {len(w['why'])} characters")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    e2e_names = {m["name"] for m in manifest["end_to_end"]}
    if "setup_s" not in e2e_names:
        bad.append("no setup_s")
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            allowed = {"name", "unit", "better", "source"}
            allowed |= ({"bound"} if section == "end_to_end"
                        else {"layer", "moves"})
            if set(m) - {"workloads"} != allowed:
                bad.append(f"metric {m.get('name')}: keys {sorted(m)}")
            if not NAME.match(m["name"]) or m["name"] in names:
                bad.append(f"metric name {m['name']!r}")
            names.add(m["name"])
            if not UNIT.match(m["unit"]):
                bad.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"metric {m['name']}: source {m['source']!r}")
            if section == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"metric {m['name']}: end-to-end source")
                if not 0.01 <= m["bound"] <= 0.1:
                    bad.append(f"metric {m['name']}: bound {m['bound']}")
            elif m["moves"] not in e2e_names:
                bad.append(f"metric {m['name']}: moves {m['moves']!r}")
            for cell in m.get("workloads", ()):
                if cell not in cells:
                    bad.append(f"metric {m['name']}: unknown cell {cell}")
            if not os.path.isfile(
                os.path.join(HERE, "metrics", m["name"] + ".py")
            ):
                bad.append(f"metric {m['name']}: no reader module")
    for w in manifest["workloads"]:
        e2e = {m["name"] for m in metrics_of(manifest, w, "end_to_end")}
        if "setup_s" not in e2e or len(e2e) < 2:
            bad.append(f"cell {w['name']}: end-to-end metrics {sorted(e2e)}")
        layer = metrics_of(manifest, w, "per_layer")
        if not layer:
            bad.append(f"cell {w['name']}: no per-layer metric")
        for m in layer:
            if m["moves"] not in e2e:
                bad.append(
                    f"cell {w['name']}: {m['name']} moves {m['moves']}, "
                    f"which the cell does not report"
                )
    return bad
