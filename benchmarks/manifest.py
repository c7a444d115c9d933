"""``BENCHMARK.json``: loading, resolving a cell to its files, and the checks
of the driver's contract that can be made without a chip. Stdlib only.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PATH = os.path.join(ROOT, "BENCHMARK.json")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH_KEY = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|"
                       r"head_dim|expansion|experts_per_tok")


def load(path: str = PATH) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(manifest: dict, workload: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_path(traffic: str) -> str:
    return os.path.join(HERE, "traffic", f"{traffic}.json")


def metrics_for(manifest: dict, section: str, workload: str) -> list[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


def reader_path(section: str, name: str) -> str:
    folder = "end_to_end" if section == "end_to_end" else "layer_metrics"
    return os.path.join(HERE, folder, f"{name}.py")


def validate(manifest: dict, root: str = ROOT) -> list[str]:
    """Problems with the manifest, as sentences; empty when it is sound."""
    bad: list[str] = []
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(manifest)} are not exactly {sorted(TOP_KEYS)}")
        return bad
    names = lambda rows: [r["name"] for r in rows]  # noqa: E731
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = names(manifest[section])
        bad += [f"{section}: name {n!r} has characters outside the allowed set"
                for n in ns if not NAME.match(n)]
        bad += [f"{section}: duplicate name {n!r}" for n in set(ns) if ns.count(n) > 1]
    both = names(manifest["end_to_end"]) + names(manifest["per_layer"])
    bad += [f"metric {n!r} is both end-to-end and per-layer" for n in set(both)
            if both.count(n) > 1]
    if not 1 <= manifest["run_seconds"] <= 51:
        bad.append("run_seconds is outside 1..51")
    for p in manifest["paths"]:
        if p.startswith("/") or ".." in p.split("/"):
            bad.append(f"path {p!r} leads out of the repo")
    under = lambda f: any(f == p or f.startswith(p + "/") for p in manifest["paths"])  # noqa: E731
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"configuration {c['name']}: keys {sorted(c)}")
        if c["name"] not in used:
            bad.append(f"configuration {c['name']} is used by no cell")
        if not under(c["file"]) or files.count(c["file"]) > 1:
            bad.append(f"configuration {c['name']}: file {c['file']!r} is outside "
                       "paths or shared")
        path = os.path.join(root, c["file"])
        if not os.path.exists(path):
            bad.append(f"configuration {c['name']}: {c['file']} does not exist")
            continue
        with open(path) as f:
            body = json.load(f)
        if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
            bad.append(f"configuration {c['name']}: reduced differs between the "
                       "manifest and its file")
        if body.get("source") != c["source"]:
            bad.append(f"configuration {c['name']}: source differs between the "
                       "manifest and its file")
        bad += [f"configuration {c['name']}: reduced names the width {k!r}"
                for k in c["reduced"] if WIDTH_KEY.search(k)]
        bad += [f"configuration {c['name']}: its file lacks {k!r}"
                for k in ("source", "reduced", "assumed") if k not in body]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    four = 0
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"cell {w['name']}: keys {sorted(w)}")
        if w["config"] not in names(manifest["configs"]):
            bad.append(f"cell {w['name']}: unknown configuration {w['config']!r}")
        if not NAME.match(w["traffic"]) or not os.path.exists(
                os.path.join(root, os.path.relpath(traffic_path(w["traffic"]), ROOT))):
            bad.append(f"cell {w['name']}: no traffic file for {w['traffic']!r}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']!r}")
        four += w["chips"] == 4
        if pairs.count((w["config"], w["traffic"])) > 1:
            bad.append(f"cell {w['name']}: its pair of configuration and traffic "
                       "appears twice")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"cell {w['name']}: why is empty, long or broken")
    if four > max(1, len(manifest["workloads"]) // 4):
        bad.append(f"{four} cells ask for 4 chips: more than a quarter, and more than one")
    cells = names(manifest["workloads"])
    e2e_cells = {}
    for section, keys in (("end_to_end", {"name", "unit", "better", "bound", "source"}),
                          ("per_layer", {"name", "unit", "better", "source", "layer", "moves"})):
        for m in manifest[section]:
            if set(m) - {"workloads"} != keys:
                bad.append(f"metric {m['name']}: keys {sorted(m)}")
                continue
            if not UNIT.match(m["unit"]):
                bad.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"metric {m['name']}: source {m['source']!r}")
            where = m.get("workloads", cells)
            bad += [f"metric {m['name']}: unknown cell {c!r}" for c in where
                    if c not in cells]
            if not os.path.exists(os.path.join(
                    root, os.path.relpath(reader_path(section, m["name"]), ROOT))):
                bad.append(f"metric {m['name']}: no reader file")
            if section == "end_to_end":
                e2e_cells[m["name"]] = set(where)
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"metric {m['name']}: an end-to-end metric is taken "
                               "by the benchmark itself")
                if not 0.01 <= m["bound"] <= 0.1:
                    bad.append(f"metric {m['name']}: bound {m['bound']}")
            else:
                moved = e2e_cells.get(m["moves"])
                if moved is None:
                    bad.append(f"metric {m['name']}: moves {m['moves']!r}, which is "
                               "no end-to-end metric")
                elif not set(where) <= moved:
                    bad.append(f"metric {m['name']}: reported in "
                               f"{sorted(set(where) - moved)}, where {m['moves']} is not")
    if "setup_s" not in e2e_cells:
        bad.append("no setup_s among the end-to-end metrics")
    for c in cells:
        have = [n for n, ws in e2e_cells.items() if c in ws]
        if "setup_s" not in have or len(have) < 2:
            bad.append(f"cell {c}: reports {have}; wants setup_s and one more")
        if not metrics_for(manifest, "per_layer", c):
            bad.append(f"cell {c}: no per-layer metric")
    return bad
