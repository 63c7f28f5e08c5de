"""BENCHMARK.json is well formed by the contract's own rules, and every
name in it leads to a file of its own. The entries kept for a later PR
(``kept/eager-cells.json``) are held to the same rules, entry by entry,
so that they can be added as they are."""

import ast
import json
import os
import re

import pytest

from . import _paths

M = _paths.manifest()
KEPT = _paths.manifest_with_kept()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E = {m["name"]: m for m in KEPT["end_to_end"]}
LAYERS = {m["name"]: m for m in KEPT["per_layer"]}
CELLS = {w["name"]: w for w in KEPT["workloads"]}
CONFIGS = {c["name"]: c for c in M["configs"]}
# A size whose change would make another model of it: never in `reduced`.
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection)_size"
                    r"|_dim\Z|_rank\Z|head_size|head_dim|expansion"
                    r"|experts_per_tok")


def cells_of(metric: dict):
    return metric.get("workloads") or list(CELLS)


def test_top_level_keys_are_exactly_the_contracts():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert len(json.dumps(M)) < 64 * 1024


def test_a_full_check_of_24_cells_fits_the_drivers_day():
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_the_manifest_stands_without_the_kept_entries():
    cells = [w["name"] for w in M["workloads"]]
    metrics = M["end_to_end"] + M["per_layer"]
    for m in metrics:
        assert all(c in cells for c in m.get("workloads", [])), m["name"]
        if "moves" in m:
            assert m["moves"] in [e["name"] for e in M["end_to_end"]]
    for cell in cells:
        reported = [m["name"] for m in M["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in M["per_layer"])
    assert {c["name"] for c in M["configs"]} == {
        w["config"] for w in M["workloads"]}
    assert len(M["end_to_end"]) <= 5        # four and setup_s (ISSUE 23)


def test_command_names_no_file_outside_paths():
    assert 1 <= len(M["command"]) <= 32
    for word in M["command"][1:]:
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(_paths.ROOT, word)):
            assert any(word.startswith(p + "/") for p in M["paths"])


@pytest.mark.parametrize("path", M["paths"])
def test_paths_are_directories_of_the_benchmarks_own(path):
    assert PATH.match(path)
    assert os.path.isdir(os.path.join(_paths.ROOT, path))
    for base, _, files in os.walk(os.path.join(_paths.ROOT, path)):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), _paths.ROOT)
            assert PATH.match(rel), rel


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_entry_and_file(name):
    c = CONFIGS[name]
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(name) and len(c["reduced"]) <= 16
    for text in (c["source"], c["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert any(c["file"].startswith(p + "/") for p in M["paths"])
    assert sum(1 for o in CONFIGS.values() if o["file"] == c["file"]) == 1
    assert any(w["config"] == name for w in CELLS.values())
    with open(os.path.join(_paths.ROOT, c["file"])) as f:
        data = json.load(f)
    assert sorted(data["reduced"]) == sorted(c["reduced"])
    for key in c["reduced"]:
        assert NAME.match(key) and not WIDTHS.search(key), key
    assert os.path.exists(os.path.join(
        _paths.BENCH, "families", data["family"] + ".py"))
    assert set(data["check"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert "rehearse" in data and "assumed" in data


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_entry_and_traffic_file(name):
    w = CELLS[name]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(w["traffic"])
    assert w["config"] in CONFIGS and w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert sum(1 for o in CELLS.values() if (o["config"], o["traffic"])
               == (w["config"], w["traffic"])) == 1
    with open(os.path.join(_paths.BENCH, "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["chips"] == w["chips"]
    assert traffic["mode"] in ("injit", "eager") and traffic["ranks"] >= 1
    reported = [m for m in E2E.values() if name in cells_of(m)]
    assert "setup_s" in {m["name"] for m in reported}
    assert len(reported) >= 2, "setup_s and one more end-to-end metric"
    assert any(name in cells_of(m) for m in LAYERS.values())


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 4)
    assert 1 <= len(M["workloads"]) <= 24 and 1 <= len(CONFIGS) <= 24


@pytest.mark.parametrize("name", sorted(E2E))
def test_end_to_end_metric(name):
    m = E2E[name]
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert all(c in CELLS for c in cells_of(m))
    assert os.path.exists(os.path.join(_paths.BENCH, "end_to_end",
                                       name + ".py"))


def test_setup_s_is_reported_by_every_cell():
    assert "workloads" not in E2E["setup_s"]
    assert 1 <= len(E2E) <= 16


def reader_constants(name):
    """LAYER, UNIT and MOVES of a reader file, read without running it."""
    with open(os.path.join(_paths.BENCH, "layer_metrics", name + ".py")) as f:
        tree = ast.parse(f.read())
    return {t.id: ast.literal_eval(node.value) for node in tree.body
            if isinstance(node, ast.Assign) for t in node.targets
            if isinstance(t, ast.Name)}


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_per_layer_metric_and_its_reader_file(name):
    m = LAYERS[name]
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    consts = reader_constants(name)
    assert (consts["LAYER"], consts["UNIT"], consts["MOVES"]) == (
        m["layer"], m["unit"], m["moves"])
    moved = E2E[m["moves"]]
    for cell in cells_of(m):
        assert cell in cells_of(moved), (
            f"{name} moves {m['moves']}, which {cell} does not report")
    if "workloads" not in m:
        assert "workloads" not in moved
    if name.endswith("_roofline") or "mfu" in name:
        assert m["unit"] == "%"


def test_names_are_unique():
    for group in (M["configs"], M["workloads"],
                  M["end_to_end"] + M["per_layer"]):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    assert 1 <= len(LAYERS) <= 128
