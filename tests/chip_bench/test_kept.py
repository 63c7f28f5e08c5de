"""How a kept cell comes in: the entries ``kept/eager-cells.json``
writes down under ``entries_for_BENCHMARK.json``, applied to a copy of
the manifest and nothing else changed, give a manifest that stands by
every rule and a cell that rehearses through the command; and the
tests' own merge of kept entries goes by name, so that neither
``kept/`` nor a test has to change when the cell moves (PR 26) or its
metrics do (PR 45). What is asserted of a manifest is asserted of the
root's and of the one the next PR would leave (``conftest.py``)."""

import copy
import json
import os

import pytest

from . import _paths
from .test_manifest import by_name, hold_to_every_rule
from .test_rehearse import check_rehearsal, run

CELLS_KEPT = _paths.kept("eager-cells.json")
ENTRIES = CELLS_KEPT["entries_for_BENCHMARK.json"]
CELL = "resnet50-eager-1rank"


def applied(m=None) -> dict:
    return _paths.apply_entries(_paths.manifest() if m is None else m,
                                ENTRIES)


def test_the_entries_are_the_cell_and_the_four_lists_it_joins():
    assert [w["name"] for w in ENTRIES["workloads"]] == [CELL]
    assert ENTRIES["workloads"] == CELLS_KEPT["workloads"]
    assert ENTRIES["append_cell_to"] == [
        "images_per_s_chip", "mfu.resnet", "device_idle_share.resnet",
        "hbm_need_gb.resnet"]
    # the kept files name no metric the manifest's readers repeat
    assert CELLS_KEPT["end_to_end"] == []
    names = [m["name"] for m in CELLS_KEPT["per_layer"]
             + _paths.kept("eager-spans.json")["per_layer"]]
    assert len(names) == 15 and not [n for n in names if ".eager" in n]


def test_the_entries_alone_make_a_manifest_that_stands_by_every_rule(
        manifest):
    m = applied(manifest)
    hold_to_every_rule(m)
    assert by_name(m, "workloads")[CELL]["chips"] == 1
    for name in ENTRIES["append_cell_to"]:
        both = by_name(m, "end_to_end") | by_name(m, "per_layer")
        assert both[name]["workloads"].count(CELL) == 1
    assert CELL not in by_name(m, "end_to_end")["step_p90_ms"]["workloads"]


@pytest.mark.parametrize("traced", [0, 1])
def test_the_kept_cell_rehearses_from_a_root_with_the_entries_alone(
        traced, tmp_path):
    """What ISSUE 25's trial failed (the family's FLOP count raising at
    the rehearsal's sizes under ``mfu.resnet``), passing; and no file
    under ``benchmarks/chip/`` changed by it."""
    bench = os.path.join(_paths.ROOT, _paths.manifest()["paths"][0])

    def files():
        out = {}
        for base, dirs, names in os.walk(bench):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for n in names:
                with open(os.path.join(base, n), "rb") as f:
                    out[os.path.join(base, n)] = f.read()
        return out

    before = files()
    m = applied()
    _paths.checkout_with(m, tmp_path)
    out = run(["--workload", CELL, "--seed", str(2**31 + 25), "--seconds",
               "1", "--trace", str(traced), "--rehearse"], cwd=tmp_path)
    check_rehearsal(out, CELL, root=tmp_path)
    if traced:
        listed = [x["name"] for x in m["per_layer"]
                  if CELL in x.get("workloads", [CELL])]
        assert {"mfu.resnet", "device_idle_share.resnet",
                "hbm_need_gb.resnet"} <= set(listed)
        assert f"per-layer readers {len(listed)} listed" in out.stdout
    assert files() == before


def test_a_cell_the_manifest_and_a_kept_file_both_have_is_one_cell(manifest):
    m = applied(manifest)         # as after the PR that moves the cell in
    merged = _paths.merge_kept(copy.deepcopy(m), CELLS_KEPT)
    assert [w["name"] for w in merged["workloads"]].count(CELL) == 1
    assert merged["workloads"] == m["workloads"]
    assert merged["end_to_end"] == m["end_to_end"]
    # The kept per-layer metrics are there once, by name as the merge
    # goes: the manifest's own as they were and in their order, a kept
    # one the manifest has not got behind them, and one it has got
    # (all of them, since PR 45) neither doubled nor moved.
    names = [x["name"] for x in merged["per_layer"]]
    mine = [x["name"] for x in m["per_layer"]]
    assert len(names) == len(set(names))
    assert names == mine + [x["name"] for x in CELLS_KEPT["per_layer"]
                            if x["name"] not in mine]
    for x in CELLS_KEPT["per_layer"]:
        got = by_name(merged, "per_layer")[x["name"]]
        assert _paths.but_workloads(got) == _paths.but_workloads(x)
        assert set(x["workloads"]) <= set(got["workloads"])
    again = _paths.merge_kept(copy.deepcopy(merged), CELLS_KEPT)
    assert again == merged
    # and before that PR the merge gives the same manifest
    assert _paths.manifest_with_kept(copy.deepcopy(manifest)) == merged
    # a manifest without them (the parent's) gets them behind its own
    names = {x["name"] for x in CELLS_KEPT["per_layer"]}
    bare = copy.deepcopy(m)
    bare["per_layer"] = [x for x in m["per_layer"] if x["name"] not in names]
    merged = _paths.merge_kept(bare, CELLS_KEPT)
    assert merged["per_layer"][-len(names):] == CELLS_KEPT["per_layer"]


def test_a_metric_both_have_gets_the_union_of_their_lists():
    m = {"workloads": [{"name": "a"}, {"name": "b"}],
         "end_to_end": [{"name": "rate", "workloads": ["a"]},
                        {"name": "setup_s"}],
         "per_layer": [{"name": "share", "workloads": ["a", "b"]}]}
    kept = {"workloads": [{"name": "b", "chips": 4}, {"name": "c"}],
            "end_to_end": [{"name": "rate", "workloads": ["c", "a"]},
                           {"name": "setup_s", "workloads": ["c"]}],
            "per_layer": [{"name": "share", "workloads": ["c"]},
                          {"name": "other", "workloads": ["c"]}]}
    out = _paths.merge_kept(m, kept)
    assert out["workloads"] == [{"name": "a"}, {"name": "b"}, {"name": "c"}]
    assert out["end_to_end"] == [
        {"name": "rate", "workloads": ["a", "c"]},
        {"name": "setup_s"}]     # every cell's stays every cell's
    assert out["per_layer"] == [
        {"name": "share", "workloads": ["a", "b", "c"]},
        {"name": "other", "workloads": ["c"]}]
    json.dumps(out)
