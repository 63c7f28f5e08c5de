"""Where the chip benchmark lives, for its tests. Importing this makes
``chipbench`` importable and touches neither JAX nor a TPU topology."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


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


def manifest_with_kept() -> dict:
    """BENCHMARK.json with what ``kept/eager-cells.json`` holds for a
    later PR merged into it: the eager cell of one rank."""
    return merge_kept(manifest(), kept("eager-cells.json"))


def checkout_with(m: dict, root) -> None:
    """A root directory whose BENCHMARK.json is ``m``: the benchmark's
    directory and the system under test are links to this repo's."""
    for name in (m["paths"][0], "horovod_tpu", "native"):
        os.makedirs(os.path.dirname(os.path.join(root, name)), exist_ok=True)
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)


def command(*args):
    """The benchmark's command as the driver spells it, from ROOT."""
    m = manifest()
    return [sys.executable if m["command"][0].startswith("python")
            else m["command"][0], *m["command"][1:], *args]
