"""``BENCHMARK.json`` is sound as the tree stands: the checks of the driver's
contract that need no chip (``benchmarks/manifest.validate``), every cell
resolves to the files it is run from, and the ration of four-chip cells.
The benchmark's own tests (``benchmarks/tests``, outside tier-1) hold the
rest; this one is in tier-1 so that a PR that edits the program cannot leave
a manifest no run can start from (asked by ISSUE 25).
"""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


@pytest.fixture(scope="module")
def manifest_mod():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_manifest", os.path.join(BENCH, "manifest.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_manifest_is_sound(manifest_mod):
    assert manifest_mod.validate(manifest_mod.load()) == []


def test_every_cell_resolves_to_its_files(manifest_mod):
    m = manifest_mod.load()
    assert m["workloads"]
    for cell in m["workloads"]:
        entry = manifest_mod.config_entry(m, cell["config"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        with open(manifest_mod.traffic_path(cell["traffic"])) as f:
            traffic = json.load(f)
        for folder, name in (("generators", traffic["generator"]),
                             ("reference", config["reference"])):
            assert os.path.exists(os.path.join(BENCH, folder, f"{name}.py")), (cell["name"], name)
        for section in ("end_to_end", "per_layer"):
            metrics = manifest_mod.metrics_for(m, section, cell["name"])
            assert metrics, (cell["name"], section)
            for metric in metrics:
                assert os.path.exists(manifest_mod.reader_path(section, metric["name"]))
        # the preset the cell's command line names is one the program has
        from ditl_tpu.models.presets import PRESETS

        assert config["preset"] in PRESETS, cell["name"]


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips(manifest_mod):
    cells = manifest_mod.load()["workloads"]
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 4)
    assert all(c["chips"] in (1, 4) for c in cells)
