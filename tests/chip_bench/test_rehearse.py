"""Every cell through the real command, at the rehearsal's tiny sizes
on the CPU; every reader a manifest lists for a cell reading that
cell's rehearsal without raising; and the command refusing to measure
where it cannot."""

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from . import _paths

M = _paths.manifest()
KEPT = _paths.manifest_with_kept()
METRIC_NAMES = [m["name"] for m in KEPT["end_to_end"] + KEPT["per_layer"]]


def run(args, cwd=_paths.ROOT, env=None, timeout=600):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS="cpu")
    return subprocess.run(_paths.command(*args), cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


# One process a cell, and every one traced (a traced rehearsal costs what
# a plain one does: nothing is profiled on the CPU, and the step is the
# same program from the same cache), so that every reader the manifest
# lists for a cell reads that cell's rehearsal through the command, with
# the program's registry on: one that raises fails the run, and the
# count the run prints is the manifest's. (``test_kept.py`` keeps a plain
# rehearsal through the command.) The eager cell came in with PR 26; a
# cell that only ``kept/`` has would run from a root whose manifest has
# its entries merged in by name. The cell of four chips is one process
# over four forced CPU devices (``run.py`` asks for them where the
# environment has not).
@pytest.mark.parametrize("cell", [w["name"] for w in KEPT["workloads"]])
def test_cell_rehearses_through_the_command(cell, tmp_path):
    root = _paths.ROOT
    if cell not in [w["name"] for w in M["workloads"]]:
        root = tmp_path
        _paths.checkout_with(KEPT, root)
    out = run(["--workload", cell, "--seed", str(2**31 + 12345),
               "--seconds", "1", "--trace", "1", "--rehearse"], cwd=root)
    check_rehearsal(out, cell, root=root)
    listed = [x for x in KEPT["per_layer"]
              if cell in x.get("workloads", [cell])]
    assert f"rehearsal: per-layer readers {len(listed)} listed for the " \
        f"cell" in out.stdout


def check_rehearsal(out, cell, root=_paths.ROOT):
    assert out.returncode == 0, out.stderr[-3000:]
    line = last_json(out.stdout)
    assert line == {"rehearse": True, "correct": True,
                    "attempted": line["attempted"], "failed": 0}
    assert line["attempted"] > 0
    # counts and `correct` only: never a device metric's name
    for name in METRIC_NAMES:
        assert f'"{name}"' not in out.stdout
    assert "phases: imports=" in out.stdout
    assert "native core: loaded=True" in out.stdout
    assert '"window"' not in out.stdout.split(
        "compilations and cache traffic by part: ")[1].splitlines()[0]
    assert not os.path.exists(os.path.join(root, ".bench_run", cell))


# What a closed set of lists in a cell's own test once guarded (a cell
# listed under a reader that misreads it) is held here for every list of
# every manifest: each (metric, cell) pair loads the metric's reader file
# and reads what the harness hands a reader behind the cell's rehearsal.
def pairs_of(m):
    cells = [w["name"] for w in m["workloads"]]
    return [(x["name"], cell) for x in m["per_layer"]
            for cell in x.get("workloads", cells)]


ROOT_PAIRS = pairs_of(M)
PAIRS = [("root", *pair) for pair in ROOT_PAIRS] + [
    ("grown", *pair) for pair in pairs_of(_paths.grown(M))
    if pair not in set(ROOT_PAIRS)]


@pytest.mark.parametrize("which, metric, cell", PAIRS)
def test_a_listed_reader_reads_its_cells_rehearsal(which, metric, cell,
                                                   manifests, monkeypatch):
    """In process and without a step run: the sizes are the rehearsal's
    own (the files' ``rehearse`` blocks laid over them, as the command
    lays them), the numbers beside them made up, and there is neither a
    trace nor a peak nor a registry, as where tracing is off. The
    reader gives a number or ``None``. (The rehearsals above do the
    same through the command for the root's pairs, with the program's
    registry on; here a pair that fails says which.) Of the grown
    manifest the pairs the root's has not: the new cell's, the new
    metric's, the grown list's."""
    from chipbench import harness
    m = manifests[which]
    assert (metric, cell) in pairs_of(m)
    monkeypatch.setattr(harness, "ROOT", _paths.root_of(m))
    monkeypatch.setattr(harness, "HERE", _paths.bench_of(m))
    spec = harness.resolve_cell(m, cell, rehearse=True)
    family = harness.load_module("families", spec["config"]["family"])
    ranks, chips = spec["traffic"]["ranks"], spec["traffic"]["chips"]
    ctx = {"spec": spec, "family": family, "peak": None, "trace": None,
           "sz": family.sizes(spec["config"],
                              spec["config"]["assumed"]["per_chip_batch"]),
           "rate": 1234.5, "steps": 6, "group": 2,
           "step_ms": [41.0, 40.0, 43.0], "window_s": 0.248,
           "phases": {p: 0.5 for p in harness.PHASES}, "setup_s": 3.0,
           "need_bytes": 123_456_789, "registry": {}, "size": ranks,
           "chips": 1 if ranks > 1 else chips, "notes": []}
    value = harness.load_module("layer_metrics", metric).read(ctx)
    assert value is None or math.isfinite(value), (metric, cell, value)


@pytest.mark.slow
def test_a_world_of_four_rehearses_through_the_launcher(tmp_path):
    """The four-rank path the harness keeps (PERF.md, Open questions):
    its cell is an entry a later PR adds, so it is added here in a copy
    of the manifest; the launcher starts four CPU processes."""
    m = _paths.manifest_with_kept()
    cell = "resnet50-eager-4rank"
    m["workloads"].append({
        "name": cell, "config": "resnet50", "traffic": "eager-4rank",
        "chips": 4, "why": "the launched world of four"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "resnet50-eager-1rank" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    _paths.checkout_with(m, tmp_path)
    out = run(["--workload", cell, "--seed", "7", "--seconds", "1",
               "--trace", "0", "--rehearse"], cwd=tmp_path)
    check_rehearsal(out, cell, root=tmp_path)
    assert "the world exited with 0; left in /dev/shm: []" in out.stdout


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    out = run(["--workload", "resnet50-injit-1chip", "--seed", "1",
               "--seconds", "1", "--trace", "0"])
    assert out.returncode != 0
    assert "found no TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_in_a_directory_of_only_the_benchmark_it_fails(tmp_path):
    """BENCHMARK.json and the files under ``paths`` alone are not the
    system under test: no result, exit code not 0."""
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), tmp_path)
    for path in M["paths"]:
        shutil.copytree(os.path.join(_paths.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = run(["--workload", "lm-injit-1chip", "--seed", "1", "--seconds",
               "1", "--trace", "0"], cwd=tmp_path, env=env)
    assert out.returncode != 0
    assert "ModuleNotFoundError" in out.stderr
    assert '"correct"' not in out.stdout


def test_unknown_workload_is_refused():
    out = run(["--workload", "no-such-cell", "--seed", "1", "--seconds",
               "1", "--trace", "0", "--rehearse"])
    assert out.returncode != 0 and '"correct"' not in out.stdout


def test_the_result_line_ends_with_each_number_beside_its_limit(capsys):
    """``checks`` comes last in the line, ``[value, limit]`` by name,
    another rank's names with its rank behind them; the same are the
    last lines on standard error; a reading that is no number stays
    valid JSON."""
    import argparse
    import importlib.util
    import math
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(_paths.BENCH, "run.py"))
    run_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_py)

    def rank(r, checks):
        return {"rank": r, "correct": all(c["ok"] for c in checks.values()),
                "digest": [1.0], "attempted": 3, "failed": 0,
                "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
                "device": {"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1},
                "memory_peak_bytes": 7, "checks": checks}

    results = [
        rank(0, {"loss_gap": {"value": 2e-5, "limit": 1e-4, "ok": True},
                 "window_loss_falls": {"value": math.inf, "limit": 0.0,
                                       "ok": False}}),
        rank(1, {"replay_loss_gap": {"value": 0.0, "limit": 0.0,
                                     "ok": True}})]
    line = run_py.last_line(argparse.Namespace(rehearse=False, trace=0),
                            M, results)
    assert list(line)[-1] == "checks" and line["correct"] is False
    assert line["checks"] == {"loss_gap": [2e-5, 1e-4],
                              "window_loss_falls": ["inf", 0.0],
                              "replay_loss_gap.r1": [0.0, 0.0]}
    assert line["device"]["count"] == 2
    json.dumps(line, allow_nan=False)
    err = capsys.readouterr().err.strip().splitlines()
    assert err == [
        "chipbench: check: rank 0 loss_gap 2e-05 limit 0.0001 ok",
        "chipbench: check: rank 0 window_loss_falls inf limit 0.0 NOT OK",
        "chipbench: check: rank 1 replay_loss_gap 0.0 limit 0.0 ok"]
