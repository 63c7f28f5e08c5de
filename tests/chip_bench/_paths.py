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


def manifest_with_kept() -> dict:
    """BENCHMARK.json with the entries that ``kept/eager-cells.json``
    holds for a later PR added to it: the eager cell of one rank."""
    m = manifest()
    with open(os.path.join(BENCH, "kept", "eager-cells.json")) as f:
        kept = json.load(f)
    for key in ("workloads", "end_to_end", "per_layer"):
        m[key] = m[key] + kept[key]
    return m


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
