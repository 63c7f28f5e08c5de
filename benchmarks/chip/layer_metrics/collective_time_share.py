"""The collective ops' device time over the traced window: a device's
mean self time under the ``all-reduce``, ``reduce-scatter``,
``all-gather`` and ``collective-permute`` events of its "XLA Ops" line,
told by name or by the opcode ``trace_reduce.short_name`` keeps
(``self_times``: an op inside a ``while`` is taken out of it, and a
collective's own children out of the collective).

What the line shows of a collective on this platform (TPU v5e, libtpu
0.0.34, my chip run, PR 25; PERF.md section 5 has the numbers): the
compiler kept the LM step's 27 all-reduces synchronous. Each is one
event and there is no ``-start``/``-done`` pair; no other op runs on
the device while one does (its self time is its whole time, and the
device's busy time is the union), so every millisecond of this share is
exposed: the step is the one-chip step plus it. Were a later compiler
or flag to split one, the ``-start`` event would be the issue and the
``-done`` event the wait for what compute did not hide (not seen here);
both are counted by what the line shows of them, and the hidden part
lies under other ops' events and in no collective's.
"""
from chipbench import trace_reduce

LAYER = "User's jitted step"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    if ctx["trace"] is None:
        return None
    per_device = [
        sum(ns for name, ns in trace_reduce.self_times(events).items()
            if trace_reduce.is_collective(name))
        for events in ctx["trace"]["events"].values()]
    return 100.0 * sum(per_device) / len(per_device) / 1e9 \
        / ctx["trace"]["window_s"]
