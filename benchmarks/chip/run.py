"""The chip benchmark's command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell of one rank runs in this process: the deployment is one process.
A cell of more ranks is started through the program's launcher
(``python -m horovod_tpu.run -np N``) before anything here imports JAX,
since a chip belongs to one process; each rank leaves its result in
``.bench_run/<cell>/``, and this process prints the last line once every
rank has exited. ``--rehearse`` runs the same code at the tiny sizes the
data files give, on the CPU, and prints counts and ``correct`` only.
"""

from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WORLD_TIMEOUT_S = 1100


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on the CPU: counts and correct only")
    p.add_argument("--rank-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--t0", type=float, default=None, help=argparse.SUPPRESS)
    return p.parse_args(argv)


def run_dir(workload: str) -> str:
    return os.path.join(ROOT, ".bench_run", workload)


def last_line(args, manifest, results) -> dict:
    """The result line from the ranks' results: rank 0's metrics, the
    worst of the ranks' verdicts, the ranks' busy time averaged."""
    first = results[0]
    correct = all(r["correct"] for r in results)
    digests = {json.dumps(r["digest"]) for r in results}
    if len(digests) != 1:
        print(f"chipbench: NOT OK: the ranks' parameters differ after "
              f"the window ({len(digests)} different sums)", flush=True)
        correct = False
    if args.rehearse:
        return {"rehearse": True, "correct": correct,
                "attempted": first["attempted"], "failed": first["failed"]}
    device = dict(first["device"])
    device["count"] = sum(r["device"]["count"] for r in results)
    device["memory_peak_bytes"] = max(r["memory_peak_bytes"]
                                      for r in results)
    line = {"correct": correct, "attempted": first["attempted"],
            "failed": first["failed"], "metrics": first["metrics"],
            "device": device}
    if args.trace:
        device["busy_s"] = sum(r["busy_s"] for r in results) / len(results)
        device["window_s"] = first["traced_window_s"]
        line["breakdown"] = first["breakdown"]
    line["checks"] = compared(results)
    return line


def compared(results) -> dict:
    """Each number the check compared beside its limit, ``{name:
    [value, limit]}``: rank 0's under their own names, another rank's
    with ``.r<rank>`` behind them. Also the run's last lines on
    standard error."""
    out = {}
    for r in results:
        for name, c in r["checks"].items():
            value = c["value"] if math.isfinite(c["value"]) \
                else repr(c["value"])
            out[name + (f".r{r['rank']}" if r["rank"] else "")] = [
                value, c["limit"]]
            print(f"chipbench: check: rank {r['rank']} {name} {value} "
                  f"limit {c['limit']} {'ok' if c['ok'] else 'NOT OK'}",
                  file=sys.stderr, flush=True)
    return out


def run_world(args, ranks: int) -> list:
    """Start the launcher's world, wait until every rank has exited,
    and read the ranks' results."""
    out = run_dir(args.workload)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()          # the ranks inherit the directory
    env = dict(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "horovod_tpu.run", "-np", str(ranks),
           sys.executable, os.path.abspath(__file__), "--rank-child",
           "--t0", repr(T0), "--workload", args.workload,
           "--seed", str(args.seed), "--seconds", repr(args.seconds),
           "--trace", str(args.trace)]
    cmd += ["--rehearse"] if args.rehearse else []
    shm_before = set(os.listdir("/dev/shm"))
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, start_new_session=True)
    try:
        rc = proc.wait(timeout=WORLD_TIMEOUT_S)
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)   # nothing outlives us
        except ProcessLookupError:
            pass
        proc.wait()
    left = sorted(set(os.listdir("/dev/shm")) - shm_before)
    print(f"chipbench: the world exited with {rc}; left in /dev/shm: "
          f"{left}", flush=True)
    if rc != 0:
        sys.exit(f"chipbench: the launcher's world exited with {rc}")
    results = []
    for r in range(ranks):
        with open(os.path.join(out, f"rank{r}.json")) as f:
            results.append(json.load(f))
    shutil.rmtree(out, ignore_errors=True)
    return results


def main(argv=None) -> None:
    args = parse(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    sys.path[:0] = [HERE, ROOT]
    if args.rank_child:
        args.launched = time.time()
        from chipbench import harness
        result = harness.run_rank(args, manifest)
        path = os.path.join(run_dir(args.workload),
                            f"rank{result['rank']}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(result, f)
        os.replace(path + ".tmp", path)
        return
    cell = {w["name"]: w for w in manifest["workloads"]}.get(args.workload)
    if cell is None:
        sys.exit(f"chipbench: no workload {args.workload!r} in "
                 f"BENCHMARK.json")
    with open(os.path.join(HERE, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    # The mix's settings of the TPU runtime: before a chip is opened
    print(f"chipbench: runtime_env {traffic.get('runtime_env')}", flush=True)
    os.environ.update(traffic.get("runtime_env", {}))
    ranks = traffic["ranks"]
    force = f"--xla_force_host_platform_device_count={traffic['chips']}"
    if (args.rehearse and ranks == 1 and traffic["chips"] > 1
            and force.split("=")[0] not in os.environ.get("XLA_FLAGS", "")):
        # one process over several chips rehearses on as many CPU devices
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + force).strip()
    if ranks > 1:
        results = run_world(args, ranks)
    else:
        from chipbench import harness
        args.launched, args.t0 = None, T0
        results = [harness.run_rank(args, manifest)]
    print(json.dumps(last_line(args, manifest, results)), flush=True)


if __name__ == "__main__":
    main()
