"""Read what the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/limits.py --workload <cell> \
        --seeds 1,2,3 --control-seeds 1,2,3 [--control-leaves lm_head]
    python3 -m horovod_tpu.run -np 4 python3 benchmarks/chip/limits.py ...

One process (one world) reads, seed by seed and at the cell's own size,
the three gaps of the sound program against the plain reference and
then the same gaps of the control: the program with its parameters
kept in bfloat16 (all of them, or with ``--control-leaves`` only those
whose path holds that word), the nearest precision below the float32
the configurations state. No window is timed. ``PERF.md`` records the
readings each limit was set from. The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--control-leaves", default="")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    sys.path[:0] = [HERE, ROOT]
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    import horovod_tpu.jax as hvd
    from chipbench import check, harness
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    spec = harness.resolve_cell(manifest, args.workload, args.rehearse)
    harness.device_line(spec["traffic"], args.rehearse)
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    limits = spec["config"]["check"]["limits"]
    rows = []
    for seed in seeds:
        reference = None
        for name, dtype in (("program", None), ("control", jnp.bfloat16)):
            if name == "control" and seed not in control:
                continue
            program = harness.Program(spec, seed, size, param_dtype=dtype,
                                      control_leaves=args.control_leaves)
            state = program.make_state()
            batch = program.make_batch(rank, program.batch_sharding)
            program.compile(state, batch)
            got = program.first_steps(state, batch)
            del got["state"], state
            gc.collect()
            if rank == 0:
                if reference is None:
                    reference = program.reference()
                row = {"seed": seed, "which": name, "losses": got["losses"],
                       **{k: v["value"] for k, v in check.compare(
                           got, reference, limits).items()}}
                rows.append(row)
                print("limits: " + json.dumps(row), flush=True)
            del program, batch
            gc.collect()
            if size > 1:
                hvd.barrier()
    if rank == 0:
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        tag = f".{args.control_leaves}" if args.control_leaves else ""
        with open(os.path.join(out, f"limits.{args.workload}{tag}.json"),
                  "w") as f:
            json.dump(rows, f, indent=1)
        for which in ("program", "control"):
            mine = [r for r in rows if r["which"] == which]
            for k in limits:
                if mine:
                    vals = [r[k] for r in mine]
                    print(f"limits: {which} {k}: min {min(vals):.3e} max "
                          f"{max(vals):.3e} over {len(vals)} seeds",
                          flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
