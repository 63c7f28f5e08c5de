"""BENCHMARK.json is well formed by the contract's own rules, and every
name in it leads to a file of its own. The entries once kept for a
later PR (``kept/eager-cells.json``) are held to the same rules, entry
by entry. Each rule is a function of a manifest and of nothing else (a
manifest knows its checkout: ``_paths.root_of``), so that a test can
hold a manifest of its own making to all of them
(:func:`hold_to_every_rule`); every test here holds two, the root's and
the one the next PR would leave (``conftest.py``), and none holds
either to a count, a place in a list or a closed set of names."""

import ast
import copy
import json
import os
import re

import pytest

from . import _paths

# Names to parametrise by, read while the tests are collected; the
# manifests the tests hold come from the ``manifests`` fixture.
NAMED = {"root": _paths.manifest_with_kept()}
NAMED["grown"] = _paths.grown(NAMED["root"])
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# A size whose change would make another model of it: never in `reduced`.
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection)_size"
                    r"|_dim\Z|_rank\Z|head_size|head_dim|expansion"
                    r"|experts_per_tok")


def by_name(m: dict, key: str) -> dict:
    return {x["name"]: x for x in m[key]}


def cells_of(m: dict, metric: dict):
    return metric.get("workloads") or [w["name"] for w in m["workloads"]]


# -- the rules, each of one manifest ----------------------------------------

def rule_top_level(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert len(json.dumps(m)) < 64 * 1024


def rule_a_full_check_of_24_cells_fits_the_drivers_day(m):
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def rule_stands_alone(m):
    cells = [w["name"] for w in m["workloads"]]
    for x in m["end_to_end"] + m["per_layer"]:
        assert all(c in cells for c in x.get("workloads", [])), x["name"]
        if "moves" in x:
            assert x["moves"] in by_name(m, "end_to_end")
    for cell in cells:
        reported = [x["name"] for x in m["end_to_end"]
                    if cell in x.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in x.get("workloads", cells)
                   for x in m["per_layer"])
    assert {c["name"] for c in m["configs"]} == {
        w["config"] for w in m["workloads"]}
    assert len(m["end_to_end"]) <= 5        # four and setup_s (ISSUE 23)


def rule_setup_s_is_reported_by_every_cell(m):
    assert "workloads" not in by_name(m, "end_to_end")["setup_s"]
    assert 1 <= len(m["end_to_end"]) <= 16


def rule_command(m):
    assert 1 <= len(m["command"]) <= 32
    for word in m["command"][1:]:
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(_paths.root_of(m), word)):
            assert any(word.startswith(p + "/") for p in m["paths"])


def rule_path(m, path):
    root = _paths.root_of(m)
    assert PATH.match(path)
    assert os.path.isdir(os.path.join(root, path))
    for base, _, files in os.walk(os.path.join(root, path)):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), root)
            assert PATH.match(rel), rel


def rule_config(m, name):
    configs = by_name(m, "configs")
    c = configs[name]
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(name) and len(c["reduced"]) <= 16
    for text in (c["source"], c["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert any(c["file"].startswith(p + "/") for p in m["paths"])
    assert sum(1 for o in configs.values() if o["file"] == c["file"]) == 1
    assert any(w["config"] == name for w in m["workloads"])
    with open(os.path.join(_paths.root_of(m), c["file"])) as f:
        data = json.load(f)
    assert sorted(data["reduced"]) == sorted(c["reduced"])
    for key in c["reduced"]:
        assert NAME.match(key) and not WIDTHS.search(key), key
    assert os.path.exists(os.path.join(
        _paths.bench_of(m), "families", data["family"] + ".py"))
    assert set(data["check"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert "rehearse" in data and "assumed" in data


def rule_cell(m, name):
    cells = by_name(m, "workloads")
    w = cells[name]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(w["traffic"])
    assert w["config"] in by_name(m, "configs") and w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert sum(1 for o in cells.values() if (o["config"], o["traffic"])
               == (w["config"], w["traffic"])) == 1
    with open(os.path.join(_paths.bench_of(m), "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["chips"] == w["chips"]
    assert traffic["mode"] in ("injit", "eager") and traffic["ranks"] >= 1
    reported = [x for x in m["end_to_end"] if name in cells_of(m, x)]
    assert "setup_s" in {x["name"] for x in reported}
    assert len(reported) >= 2, "setup_s and one more end-to-end metric"
    assert any(name in cells_of(m, x) for x in m["per_layer"])


def rule_at_most_a_quarter_of_the_cells_take_four_chips(m):
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert 1 <= len(m["workloads"]) <= 24 and 1 <= len(m["configs"]) <= 24


def rule_names_are_unique(m):
    assert 1 <= len(m["per_layer"]) <= 128
    for group in (m["configs"], m["workloads"],
                  m["end_to_end"] + m["per_layer"]):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))


def rule_end_to_end(m, name):
    x = by_name(m, "end_to_end")[name]
    assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(name) and UNIT.match(x["unit"])
    assert x["better"] in ("lower", "higher")
    assert x["source"] in ("host_clock", "device_trace")
    assert 0.01 <= x["bound"] <= 0.1
    assert all(c in by_name(m, "workloads") for c in cells_of(m, x))
    assert os.path.exists(os.path.join(_paths.bench_of(m), "end_to_end",
                                       name + ".py"))


def reader_constants(name, m=None):
    """LAYER, UNIT and MOVES of a reader file, read without running it."""
    with open(os.path.join(_paths.bench_of(m), "layer_metrics",
                           name + ".py")) as f:
        tree = ast.parse(f.read())
    return {t.id: ast.literal_eval(node.value) for node in tree.body
            if isinstance(node, ast.Assign) for t in node.targets
            if isinstance(t, ast.Name)}


def rule_per_layer(m, name):
    x = by_name(m, "per_layer")[name]
    assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(name) and UNIT.match(x["unit"])
    assert x["better"] in ("lower", "higher") and x["source"] in SOURCES
    assert 1 <= len(x["layer"]) <= 200 and "\n" not in x["layer"]
    consts = reader_constants(name, m)
    assert (consts["LAYER"], consts["UNIT"], consts["MOVES"]) == (
        x["layer"], x["unit"], x["moves"])
    moved = by_name(m, "end_to_end")[x["moves"]]
    for cell in cells_of(m, x):
        assert cell in cells_of(m, moved), (
            f"{name} moves {x['moves']}, which {cell} does not report")
    if "workloads" not in x:
        assert "workloads" not in moved
    if name.endswith("_roofline") or "mfu" in name:
        assert x["unit"] == "%"


WHOLE = (rule_top_level, rule_a_full_check_of_24_cells_fits_the_drivers_day,
         rule_stands_alone, rule_setup_s_is_reported_by_every_cell,
         rule_command, rule_at_most_a_quarter_of_the_cells_take_four_chips,
         rule_names_are_unique)


def hold_to_every_rule(m):
    """Every rule above, of a manifest a test has made."""
    for rule in WHOLE:
        rule(m)
    for key, rule in (("configs", rule_config), ("workloads", rule_cell),
                      ("end_to_end", rule_end_to_end),
                      ("per_layer", rule_per_layer)):
        for name in by_name(m, key):
            rule(m, name)
    for path in m["paths"]:
        rule_path(m, path)


# -- the manifest as it is and as the next PR would leave it, each with the
# -- once-kept entries merged in ---------------------------------------------

def names_of(key):
    return [(which, name) for which, m in NAMED.items()
            for name in sorted(by_name(m, key))]


@pytest.fixture(scope="session")
def kept(manifests):
    """``{"root", "grown"}`` with the once-kept entries merged in (no
    rule changes what it is given)."""
    return {which: _paths.manifest_with_kept(copy.deepcopy(m))
            for which, m in manifests.items()}


@pytest.mark.parametrize("which", list(NAMED))
@pytest.mark.parametrize("rule", WHOLE, ids=lambda r: r.__name__)
def test_the_manifest_as_a_whole(rule, which, manifests):
    rule(manifests[which])


@pytest.mark.parametrize("which", list(NAMED))
def test_the_merged_manifest_has_each_name_once(which, kept):
    rule_names_are_unique(kept[which])
    assert kept[which] == NAMED[which]      # what the names were read from


@pytest.mark.parametrize("which", list(NAMED))
@pytest.mark.parametrize("path", NAMED["root"]["paths"])
def test_paths_are_directories_of_the_benchmarks_own(path, which, manifests):
    rule_path(manifests[which], path)


@pytest.mark.parametrize("which, name", names_of("configs"))
def test_config_entry_and_file(which, name, kept):
    rule_config(kept[which], name)


@pytest.mark.parametrize("which, name", names_of("workloads"))
def test_cell_entry_and_traffic_file(which, name, kept):
    rule_cell(kept[which], name)


@pytest.mark.parametrize("which, name", names_of("end_to_end"))
def test_end_to_end_metric(which, name, kept):
    rule_end_to_end(kept[which], name)


@pytest.mark.parametrize("which, name", names_of("per_layer"))
def test_per_layer_metric_and_its_reader_file(which, name, kept):
    rule_per_layer(kept[which], name)
