"""A later PR adds a configuration, a traffic mix, a cell and a
per-layer metric by new files and new entries alone: shown here in a
temporary copy of the benchmark, with no file of it edited. The tests
are files of the benchmark too: the manifest such a PR leaves
(``_paths.grown``) is what every test of this directory that asserts
anything of a manifest also runs on (the ``manifest`` fixture of
``conftest.py``), and here it is held to be that manifest."""

import json
import os
import shutil
import subprocess

from . import _paths
from .test_manifest import by_name, hold_to_every_rule

NEW_METRIC = '''"""Negotiation cycles the runtime ran a step."""
LAYER = "Eager adapter and cycle"
UNIT = "count"
MOVES = "images_per_s_chip"


def read(ctx):
    cycles = ctx["registry"].get("hvd_cycles_total")
    return None if not cycles else cycles / ctx["steps"]
'''


def test_new_files_and_entries_alone_make_a_new_cell(tmp_path):
    m = _paths.manifest_with_kept()
    for path in m["paths"][:1]:
        shutil.copytree(os.path.join(_paths.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("horovod_tpu", "native"):      # the system under test
        os.symlink(os.path.join(_paths.ROOT, name), tmp_path / name)
    bench = tmp_path / m["paths"][0]
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    # a configuration: ResNet-18-like stages of the same family
    with open(bench / "configs" / "resnet50.json") as f:
        config = json.load(f)
    config["stage_sizes"] = [2, 2, 2, 2]
    config["rehearse"]["stage_sizes"] = [1, 1, 1]
    # a configuration brings its own limits (fp16 gradients, other depth)
    config["rehearse"]["check"]["limits"]["loss_gap"] = 0.05
    (bench / "configs" / "resnet-other.json").write_text(json.dumps(config))
    # a traffic mix: the eager world of one with upstream's fp16 option
    with open(bench / "traffic" / "eager-1rank.json") as f:
        traffic = json.load(f)
    traffic["fp16_allreduce"] = True
    (bench / "traffic" / "eager-1rank-fp16.json").write_text(
        json.dumps(traffic))
    # a per-layer metric: one reader file
    (bench / "layer_metrics" / "cycles_per_step.py").write_text(NEW_METRIC)
    # and the entries that name them
    m["configs"].append({
        "name": "resnet-other", "source": "a test's own",
        "file": f"{m['paths'][0]}/configs/resnet-other.json",
        "reduced": [], "why": "shows a configuration is a file"})
    m["workloads"].append({
        "name": "resnet-other-eager-fp16", "config": "resnet-other",
        "traffic": "eager-1rank-fp16", "chips": 1,
        "why": "shows a cell is an entry"})
    cell = "resnet-other-eager-fp16"
    for metric in m["end_to_end"] + m["per_layer"]:
        if "resnet50-eager-1rank" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    m["per_layer"].append({
        "name": "cycles_per_step", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "Eager adapter and cycle",
        "moves": "images_per_s_chip", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        _paths.command("--workload", cell, "--seed", "9", "--seconds", "1",
                       "--trace", "1", "--rehearse"),
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 0, out.stdout
    listed = sum(1 for x in m["per_layer"]
                 if cell in x.get("workloads", [cell]))
    assert f"per-layer readers {listed} listed" in out.stdout
    after = {p: p.read_bytes() for p in bench.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts
             and p in before}
    assert after == {p: b for p, b in before.items() if p in after}


def test_the_grown_manifest_is_what_the_next_pr_leaves(manifests):
    """One more configuration with a file of its own, one more one-chip
    cell behind the newest in every list the newest is in, one more
    per-layer metric that lists old cells, one old list grown by an old
    cell, nothing else moved; it stands by every rule, and its checkout
    holds this repo's benchmark by links and two new files."""
    m, g = manifests["root"], manifests["grown"]
    assert _paths.root_of(m) == _paths.ROOT != _paths.root_of(g)
    hold_to_every_rule(g)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert [x["name"] for x in g[key]][:len(m[key])] \
            == [x["name"] for x in m[key]]
    assert [c["name"] for c in g["configs"]][len(m["configs"]):] \
        == [_paths.NEXT_CONFIG]
    assert g["workloads"][len(m["workloads"]):] == [
        dict(g["workloads"][-1], name=_paths.NEXT_CELL, chips=1,
             config=_paths.NEXT_CONFIG)]
    assert [x["name"] for x in g["per_layer"]][len(m["per_layer"]):] \
        == [_paths.NEXT_METRIC]
    old_cells = set(by_name(m, "workloads"))
    assert set(g["per_layer"][-1]["workloads"]) & old_cells
    grew = {}
    for was, now in zip(m["end_to_end"] + m["per_layer"],
                        g["end_to_end"] + g["per_layer"]):
        assert _paths.but_workloads(was) == _paths.but_workloads(now)
        ours, theirs = was.get("workloads", []), now.get("workloads", [])
        assert theirs[:len(ours)] == ours
        if _paths.NEWEST[1] in ours:
            assert theirs[len(ours):] == [_paths.NEXT_CELL], was["name"]
        elif theirs != ours:
            grew[was["name"]] = theirs[len(ours):]
    assert grew == {_paths.GROWN_LIST[0]: [_paths.GROWN_LIST[1]]}
    assert _paths.GROWN_LIST[1] in old_cells
    # the checkout: links to this repo's files, and the two new ones
    bench = _paths.bench_of(g)
    own = [os.path.relpath(os.path.join(base, f), bench)
           for base, _, files in os.walk(bench) for f in files
           if not os.path.islink(os.path.join(base, f))]
    assert sorted(own) == [f"configs/{_paths.NEXT_CONFIG}.json",
                           f"layer_metrics/{_paths.NEXT_METRIC}.py"]
    with open(os.path.join(_paths.root_of(g), "BENCHMARK.json")) as f:
        assert json.load(f) == g
