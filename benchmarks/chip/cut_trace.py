"""Cut a trace of the chip down to the fixture the reduction's test holds.

    python3 benchmarks/chip/cut_trace.py --workload <cell> --seed <n> \
        --out benchmarks/chip/testdata/trace_small.json

Runs the cell's traced run in this process on the chip (one rank), keeps
what ``trace_reduce.reduce_trace`` made of the profiler's trace, and
writes the window's first timed groups as a trace the reduction reads
again, with the numbers it reduces to beside it. The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT]

from chipbench import trace_reduce  # noqa: E402


def trace_summary(reduced: dict, steps: int = 2) -> dict:
    """A cut of the trace small enough to keep: the window's first
    ``steps`` timed groups, as a trace the reduction reads again (the
    recorded trace the tests hold), with what it reduces to beside it."""
    groups = [s for s in reduced["spans"] if s[0] == "bench.step"]
    t0 = groups[0][1]
    t1 = groups[min(steps, len(groups)) - 1]
    t1 = t1[1] + t1[2]
    spans = [list(s) for s in trace_reduce.clip(reduced["spans"], t0, t1)
             if s[0] != trace_reduce.WINDOW_SPAN]
    spans.insert(0, [trace_reduce.WINDOW_SPAN, t0, t1 - t0])
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": spans}]}]}
    for device, events in reduced["events"].items():
        trace["planes"].append({"name": device, "lines": [
            {"name": trace_reduce.OPS_LINE,
             "events": [list(e) for e in
                        trace_reduce.clip(events, t0, t1)]}]})
    again = trace_reduce.reduce_trace(trace)
    return {"trace": trace,
            "expect": {"window_s": again["window_s"],
                       "busy_s": again["busy_s"],
                       "top_op": again["device_ops"][0][0],
                       "device_ops": again["device_ops"],
                       "idle_gaps": again["idle_gaps"]},
            "whole": {"window_s": reduced["window_s"],
                      "busy_s": reduced["busy_s"],
                      "device_ops": reduced["device_ops"],
                      "idle_gaps": reduced["idle_gaps"]}}


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args()
    from chipbench import harness
    kept = {}
    reduce_trace = trace_reduce.reduce_trace

    def keep(trace):
        kept.update(reduce_trace(trace))
        return kept

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    run = argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=4.0, trace=1,
        rehearse=False, t0=time.time(), launched=None)
    trace_reduce.reduce_trace = keep
    try:
        harness.run_rank(run, manifest)
    finally:
        trace_reduce.reduce_trace = reduce_trace
    with open(args.out, "w") as f:
        json.dump(trace_summary(kept), f)


if __name__ == "__main__":
    main()
