"""Where the chip benchmark lives, for its tests. Importing this makes
``chipbench`` importable and touches neither JAX nor a TPU topology."""

import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


class Manifest(dict):
    """A BENCHMARK.json with the root of the checkout it lies in: the
    directory its ``paths``, its command's words and its
    configurations' ``file`` start from."""
    root = ROOT


def root_of(m: dict) -> str:
    return getattr(m, "root", ROOT)


def bench_of(m: dict) -> str:
    """The benchmark's directory in the checkout ``m`` lies in."""
    return os.path.join(root_of(m), "benchmarks", "chip")


def manifest() -> Manifest:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return Manifest(json.load(f))


def listed_for(m: dict, cell: str) -> list:
    """The names of the metrics whose ``workloads`` name ``cell``, once
    for each time they do."""
    return [x["name"] for x in m["end_to_end"] + m["per_layer"]
            for c in x.get("workloads", ()) if c == cell]


def but_workloads(entry: dict) -> dict:
    """A metric's entry without the cells it is listed for."""
    return {k: v for k, v in entry.items() if k != "workloads"}


def kept(name: str) -> dict:
    with open(os.path.join(BENCH, "kept", name)) as f:
        return json.load(f)


def apply_entries(m: dict, entries: dict) -> dict:
    """``m`` with a kept file's ``entries_for_BENCHMARK.json`` applied
    as that key says: each cell appended, and its name appended to the
    ``workloads`` list of each metric named. A cell or a name that is
    there already stays as it is, once."""
    have = {w["name"] for w in m["workloads"]}
    new = [w for w in entries["workloads"] if w["name"] not in have]
    m["workloads"] = m["workloads"] + new
    metrics = {x["name"]: x for x in m["end_to_end"] + m["per_layer"]}
    for name in entries["append_cell_to"]:
        lists = metrics[name]["workloads"]
        lists += [w["name"] for w in entries["workloads"]
                  if w["name"] not in lists]
    return m


def merge_kept(m: dict, kept: dict) -> dict:
    """``m`` with a kept file's entries merged in by name: a cell or a
    metric is added only where ``m`` has none of that name, and a metric
    both have gets the union of their ``workloads``. So a kept entry
    that has since moved into BENCHMARK.json neither doubles nor has to
    leave ``kept/``."""
    if "entries_for_BENCHMARK.json" in kept:
        apply_entries(m, kept["entries_for_BENCHMARK.json"])
    for key in ("workloads", "end_to_end", "per_layer"):
        have = {x["name"]: x for x in m[key]}
        for entry in kept[key]:
            mine = have.get(entry["name"])
            if mine is None:
                m[key] = m[key] + [entry]
            elif "workloads" in mine and "workloads" in entry:
                mine["workloads"] += [c for c in entry["workloads"]
                                      if c not in mine["workloads"]]
    return m


def manifest_with_kept(m: dict = None) -> dict:
    """``m`` (BENCHMARK.json where none is given) with what
    ``kept/eager-cells.json`` held for a later PR merged into it: the
    eager cell of one rank and its metrics, which have all moved into
    the manifest since (PR 26 the cell, PR 45 the metrics), so that the
    merge adds nothing now."""
    return merge_kept(manifest() if m is None else m,
                      kept("eager-cells.json"))


def checkout_with(m: dict, root) -> None:
    """A root directory whose BENCHMARK.json is ``m``: the benchmark's
    directory and the system under test are links to this repo's."""
    for name in (m["paths"][0], "horovod_tpu", "native"):
        os.makedirs(os.path.dirname(os.path.join(root, name)), exist_ok=True)
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)


# -- the manifest as the next PR would leave it -----------------------------
# The newest cell, its configuration and what a PR like the one that
# brought it adds behind it: a configuration of the same family, a cell
# appended to every list the newest is in, a per-layer metric that lists
# old cells, and an old metric's list grown by an old cell.
# (The new names are ones no PR would give its own: a PR that did would
# meet them twice in the grown copy.)
NEWEST = ("ling-3.0-flash-ep64-l7", "ling3flash-injit-1chip")
NEXT_CONFIG, NEXT_CELL = "a-later-prs-model-l7", "alaterprs-injit-1chip"
NEXT_METRIC = "a_later_prs_scope_ms_per_step"
NEXT_METRIC_OLD_CELLS = ["qwen3next-injit-1chip", "lfm2moe-injit-1chip",
                         "ling3flash-injit-1chip"]
GROWN_LIST = ("moe_layer_ms_per_step", "glm47flash-injit-1chip")
NEXT_READER = '''"""Device time a step under the scope ``optimizer``."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("optimizer",))
'''


def grown(m: dict) -> dict:
    """A copy of ``m`` as a later PR would leave it, by entries alone:
    one more configuration, one more one-chip cell behind the newest in
    every list the newest is in, one more ``per_layer`` entry that
    lists old cells too, and one old metric's list grown by an old
    cell. Its two new files are :func:`checkout_grown`'s to write."""
    g = copy.deepcopy(m)
    config = dict({c["name"]: c for c in g["configs"]}[NEWEST[0]],
                  name=NEXT_CONFIG, why="what the next PR's model is",
                  source="a test's own: the newest configuration again",
                  file=f"{g['paths'][0]}/configs/{NEXT_CONFIG}.json")
    g["configs"].append(config)
    g["workloads"].append({
        "name": NEXT_CELL, "config": NEXT_CONFIG, "traffic": "injit-1chip",
        "chips": 1, "why": "what the next PR's cell is"})
    metrics = {x["name"]: x for x in g["end_to_end"] + g["per_layer"]}
    for x in metrics.values():
        if NEWEST[1] in x.get("workloads", ()):
            x["workloads"].append(NEXT_CELL)
    g["per_layer"].append(dict(
        metrics["kda_outside_kernels_ms_per_step"], name=NEXT_METRIC,
        workloads=NEXT_METRIC_OLD_CELLS + [NEXT_CELL]))
    metrics[GROWN_LIST[0]]["workloads"].append(GROWN_LIST[1])
    return g


def checkout_grown(m: dict, root) -> Manifest:
    """:func:`grown` of ``m`` in a checkout of its own under ``root``:
    the benchmark's directory there is this repo's by links, file for
    file in ``configs/`` and ``layer_metrics/``, beside the new
    configuration's file (the newest's again) and the new reader's.
    No file of this repo is written."""
    g = Manifest(grown(m))
    g.root = str(root)
    for name in ("horovod_tpu", "native", g["paths"][1]):
        os.makedirs(os.path.dirname(os.path.join(g.root, name)),
                    exist_ok=True)
        os.symlink(os.path.join(ROOT, name), os.path.join(g.root, name))
    with open(os.path.join(BENCH, "configs", f"{NEWEST[0]}.json")) as f:
        new = {"configs": {f"{NEXT_CONFIG}.json": f.read()},
               "layer_metrics": {f"{NEXT_METRIC}.py": NEXT_READER}}
    bench = bench_of(g)
    os.makedirs(bench)
    for name in os.listdir(BENCH):
        if name == "__pycache__":
            continue
        if name not in new:
            os.symlink(os.path.join(BENCH, name), os.path.join(bench, name))
            continue
        os.mkdir(os.path.join(bench, name))
        for f in os.listdir(os.path.join(BENCH, name)):
            if f != "__pycache__":
                os.symlink(os.path.join(BENCH, name, f),
                           os.path.join(bench, name, f))
        for f, text in new[name].items():     # "x": never through a link
            with open(os.path.join(bench, name, f), "x") as out:
                out.write(text)
    with open(os.path.join(g.root, "BENCHMARK.json"), "w") as f:
        json.dump(g, f)
    return g


def command(*args):
    """The benchmark's command as the driver spells it, from ROOT."""
    m = manifest()
    return [sys.executable if m["command"][0].startswith("python")
            else m["command"][0], *m["command"][1:], *args]
