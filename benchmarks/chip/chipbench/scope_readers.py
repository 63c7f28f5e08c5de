"""Device time by scope: what the readers of the program's device-side
scopes share.

The program opens one vocabulary of scopes on the device
(``horovod_tpu/common/trace.py`` ``DEVICE_SCOPES``, docs/tracing.md)
and says which scope each instruction of its step's executable belongs
to (``spmd.noted_device_scopes()``: the step built under an armed
trace notes what it compiles, and the table is made when first asked
for, which is here, behind the window). The harness's reduced trace
keeps every device event under its instruction's name, and
``trace_reduce.self_times`` takes nested events out of their parents,
so a ``while`` or a conditional keeps only what its body's ops leave
and a scope that runs in a loop is read once. A program without such a
table (this benchmark laid over an older commit, the eager cell, a
rehearsal) gives every reader here ``None``.
"""

from __future__ import annotations

import json
import time

from chipbench import trace_reduce

COVERED = 0.99          # of the device's self time, or no value
UNSCOPED = ""
_KEY = "device_scopes"  # where the first reader leaves its work in ctx


def noted_table():
    """The program's ``{instruction: scope}`` for the step it compiled
    last under an armed trace, or ``None``."""
    from horovod_tpu import spmd
    noted = getattr(spmd, "noted_device_scopes", None)
    return noted() if noted else None


def by_event(ctx):
    """``{event name: (scope, ns)}``, a device's mean self time of each
    event of the window with the scope the program's table gives its
    instruction (``None``: the table does not know it); ``None``
    without a trace or a table, or where the table knows less than
    ``COVERED`` of the device's self time (another executable's). The
    first call does the work and adds the ``scopes:`` note."""
    if _KEY not in ctx:
        ctx[_KEY] = _by_event(ctx)
    return ctx[_KEY]


def _by_event(ctx):
    if ctx.get("trace") is None:
        return None
    t0 = time.perf_counter()
    table = noted_table()
    built_s = time.perf_counter() - t0
    if not table:
        return None
    from horovod_tpu import spmd
    devices = ctx["trace"]["events"]
    times: dict = {}
    for events in devices.values():
        for name, ns in trace_reduce.self_times(events).items():
            times[name] = times.get(name, 0.0) + ns / len(devices)
    found = {name: (spmd.scope_of(table, name), ns)
             for name, ns in times.items()}
    total = sum(times.values())
    known = sum(ns for scope, ns in found.values() if scope is not None)
    if not total or known < COVERED * total:
        ctx["notes"].append(
            f"scopes: the program's table knows {known / 1e6:.3f} ms of "
            f"{total / 1e6:.3f} ms of device self time, under "
            f"{COVERED:.0%}: it is another executable's, no value")
        return None
    backward = getattr(table, "backward", frozenset())
    scopes, behind = {}, {}
    for name, (scope, ns) in found.items():
        scope = scope or UNSCOPED
        scopes[scope] = scopes.get(scope, 0.0) + ns
        if name.split("[", 1)[0] in backward:
            behind[scope] = behind.get(scope, 0.0) + ns

    def ms(d):
        return {k: round(v / 1e6 / ctx["steps"], 3) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    ctx["notes"].append(
        f"scopes: {json.dumps(ms(scopes))} ms a step by innermost scope "
        f"(self times, a device's mean over {ctx['steps']} steps; "
        f"{UNSCOPED!r} is the unscoped rest); of which under the "
        f"backward's transpose {json.dumps(ms(behind))}; sum "
        f"{total / 1e6 / ctx['steps']:.3f} ms a step against busy "
        f"{1e3 * ctx['trace']['busy_s'] / ctx['steps']:.3f}; the table "
        f"knows {100.0 * known / total:.3f}% of it ({len(table)} "
        f"instructions from {getattr(table, 'text_bytes', 0)} bytes of "
        f"the executable's text, read in {built_s:.3f} s)")
    return found


def scope_ms_per_step(ctx, scopes, less=None):
    """Device milliseconds a step under ``scopes`` (each a scope's name,
    or a prefix that ends in ``.``; ``""`` is the unscoped rest, with
    what the table does not know), less the events whose name starts
    with ``less`` (0.0 where no event lies under them); ``None`` where
    :func:`by_event` gives none."""
    found = by_event(ctx)
    if found is None:
        return None

    def wanted(scope):
        return any(scope == s or (s.endswith(".") and scope.startswith(s))
                   for s in scopes)

    return sum(ns for name, (scope, ns) in found.items()
               if wanted(scope or UNSCOPED)
               and not (less and name.startswith(less))) \
        / 1e6 / ctx["steps"]
