"""The program's one span call (common/trace.py ``span``/``interval``):
off, every site gets the shared no-op and reads no clock; on, a world
of one leaves a ring whose spans nest, share their exchange's cycle and
agree with ``hvd_span_seconds``; the ring outlives ``hvd.shutdown()``
and counts what it drops."""

import ast
import os
import re
import time

import numpy as np
import pytest

import horovod_tpu.jax as hvd
from horovod_tpu.common import basics
from horovod_tpu.common import metrics as hmetrics
from horovod_tpu.common import trace as htrace

PKG = os.path.dirname(os.path.abspath(hvd.__file__ + "/.."))
LEAVES = {f"w{i}": np.full((3, i + 1), float(i), np.float32)
          for i in range(20)}


@pytest.fixture
def fresh_ring():
    hvd.shutdown()
    htrace._reset_spans_for_tests()
    yield
    hvd.shutdown()
    htrace._reset_spans_for_tests()


def span_literals():
    """Every ``"hvd.<name>"`` string literal in the package outside
    trace.py, with its file: the sites that open the vocabulary."""
    found = {}
    for base, _, files in os.walk(PKG):
        for f in files:
            path = os.path.join(base, f)
            if not f.endswith(".py") or path.endswith("common/trace.py"):
                continue
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str) \
                        and re.fullmatch(r"hvd\.[a-z_.]+", node.value):
                    found.setdefault(node.value, set()).add(
                        os.path.relpath(path, PKG))
    return found


def test_every_span_of_the_vocabulary_has_a_site_and_no_site_another():
    sites = span_literals()
    assert set(sites) == set(htrace.SPAN_COUNTS), (
        set(sites) ^ set(htrace.SPAN_COUNTS))
    assert sites["hvd.init"] == {"common/basics.py"}
    assert sites["hvd.enqueue"] == {"ops/__init__.py"}
    assert sites["hvd.pack"] == sites["hvd.unpack"] == {"ops/backend.py"}
    assert sites["hvd.execute"] == {"ops/operation_manager.py"}
    assert "common/runtime.py" in sites["hvd.cycle"]


def test_off_every_site_gets_the_shared_noop_and_reads_no_clock(
        fresh_ring, monkeypatch):
    """Tracing off (the default): a whole start-up, broadcast, exchange
    and shutdown construct no span, read the spans' clock never, and
    leave the ring as it was."""
    assert os.environ.get("HOROVOD_TPU_METRICS", "0") != "1"
    assert not os.environ.get("HOROVOD_TPU_TRACE")

    def boom(*a, **kw):
        raise AssertionError("a span was made with tracing off")

    monkeypatch.setattr(htrace, "_Span", boom)
    monkeypatch.setattr(htrace, "_close", boom)
    hvd.init()
    rt = basics.runtime()
    assert rt.metrics is hmetrics.NOOP_REGISTRY and not rt._trace_on
    for name in htrace.SPAN_COUNTS:
        assert htrace.span(name, 1, 2, 3, "tag") is htrace.NOOP_SPAN
    assert htrace.span_clock_ns() == 0
    htrace.interval("hvd.queue_wait", 1, 2)
    hvd.broadcast_parameters(LEAVES)
    out = hvd.allreduce_gradients(LEAVES)
    np.testing.assert_array_equal(out["w3"], LEAVES["w3"])
    assert rt.tensor_table.popped_queued_ns == 0
    hvd.shutdown()
    assert htrace.recent_spans() == [] and htrace.spans_dropped() == 0
    # the no-op takes what sites set on it, and keeps nothing
    htrace.NOOP_SPAN.cycle = 7
    assert not hasattr(htrace.NOOP_SPAN, "cycle")
    assert htrace.NOOP_SPAN.start_ns == htrace.NOOP_SPAN.end_ns == 0


def run_world_of_one(monkeypatch):
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    hvd.init()
    rt = basics.runtime()
    hvd.broadcast_parameters(LEAVES)
    hvd.allreduce_gradients(LEAVES)
    out = hvd.allreduce_gradients(LEAVES)
    np.testing.assert_array_equal(out["w5"], LEAVES["w5"])
    hvd.shutdown()
    return rt, htrace.recent_spans()


def test_on_a_world_of_one_leaves_a_ring_that_nests_and_counts(
        fresh_ring, monkeypatch):
    rt, ring = run_world_of_one(monkeypatch)
    assert htrace.spans_dropped() == 0
    by_id = {r.id: r for r in ring}
    names = {r.name for r in ring}
    # a world of one packs nothing; everything else is there
    assert names >= set(htrace.SPAN_COUNTS) - {
        "hvd.pack", "hvd.unpack", "hvd.hold"}, names
    for r in ring:
        assert r.name in htrace.SPAN_COUNTS and r.end_ns >= r.start_ns
        if r.parent:
            parent = by_id[r.parent]
            assert parent.thread == r.thread
            assert parent.start_ns <= r.start_ns <= r.end_ns <= parent.end_ns
    executes = [r for r in ring if r.name == "hvd.execute"]
    assert executes
    for r in executes:
        assert by_id[r.parent].name == "hvd.cycle"
        assert r.counts["tensors"] >= 1 and r.counts["tag"].endswith("/local")
    init = next(r for r in ring if r.name == "hvd.init")
    assert init.counts == {"ranks": 1} and init.parent == 0
    assert {by_id[r.parent].name for r in ring
            if r.name.startswith("hvd.init.")} == {"hvd.init"}
    # the spans of one exchange share its world cycle, on both threads
    exchanges = [r for r in ring if r.name == "hvd.allreduce_gradients"]
    assert len(exchanges) == 2
    for ex in exchanges:
        assert ex.cycle > 0 and ex.thread == "MainThread"
        assert ex.counts == {"leaves": 20, "bytes": sum(
            a.nbytes for a in LEAVES.values())}
        inside = [r for r in ring if r.parent == ex.id]
        waits = [r for r in inside if r.name == "hvd.synchronize"]
        assert len(waits) == 20 and {r.cycle for r in waits} == {ex.cycle}
        assert [r.counts for r in inside if r.name == "hvd.enqueue"] == [
            {"tensors": 20}]
        shared = {r.name: r for r in ring if r.cycle == ex.cycle
                  and r.thread != "MainThread"}
        assert set(shared) >= {"hvd.queue_wait", "hvd.cycle",
                               "hvd.negotiate", "hvd.execute",
                               "hvd.complete"}, shared
        assert shared["hvd.queue_wait"].thread == ""
        assert shared["hvd.queue_wait"].end_ns == shared["hvd.cycle"].start_ns
        assert shared["hvd.cycle"].counts == {"requests": 20}
        assert shared["hvd.cycle"].thread == "hvd-background"
    assert exchanges[0].cycle < exchanges[1].cycle
    # one histogram family, whose counts are the ring's; the older
    # names are observed by the same spans
    snap = rt.metrics.snapshot()
    for name in names:
        rec = snap[f'hvd_span_seconds{{span="{name}"}}']
        in_ring = [r for r in ring if r.name == name]
        assert rec["count"] == len(in_ring), name
        assert rec["sum"] == pytest.approx(
            sum(r.end_ns - r.start_ns for r in in_ring) * 1e-9), name

    def same(old, span):
        new = snap[f'hvd_span_seconds{{span="{span}"}}']
        return (snap[old]["count"], snap[old]["sum"]) == (
            new["count"], pytest.approx(new["sum"]))

    assert same("hvd_cycle_seconds", "hvd.cycle")
    assert same("hvd_negotiation_seconds", "hvd.negotiate")
    walls = [v for k, v in snap.items()
             if k.startswith("hvd_collective_seconds")]
    assert sum(w["count"] for w in walls) == len(executes)


def test_the_ring_outlives_shutdown_and_counts_what_it_drops(
        fresh_ring, monkeypatch):
    _, ring = run_world_of_one(monkeypatch)
    assert not basics.initialized() and len(ring) > 60
    assert htrace.recent_spans() == ring          # still there, and again
    htrace._reset_spans_for_tests(capacity=8)
    htrace.arm_spans(True)
    for i in range(20):
        with htrace.span("hvd.hold", cycle=i):
            pass
    kept = htrace.recent_spans()
    assert [r.cycle for r in kept] == list(range(12, 20))
    assert htrace.spans_dropped() == 12


def test_an_interval_is_no_threads_and_a_child_inherits_its_cycle(
        fresh_ring):
    htrace.arm_spans(True)
    with htrace.span("hvd.execute", 9, 2) as sp:
        assert sp.on and htrace.current_cycle() == 9
        with htrace.span("hvd.pack") as pack:
            pack.nbytes = 64
        t0 = htrace.span_clock_ns()
    assert abs(t0 - time.time_ns()) < 10**9 and htrace.current_cycle() == 0
    htrace.interval("hvd.queue_wait", 10, 30, 9, 5)
    pack, execute, wait = htrace.recent_spans()
    assert (pack.name, pack.parent, pack.cycle, pack.counts) == (
        "hvd.pack", execute.id, 9, {"bytes": 64})
    assert execute.counts == {"tensors": 2} and execute.parent == 0
    assert wait[:7] == ("hvd.queue_wait", 10, 30, "", 0, 9, {"tensors": 5})


def test_a_pack_is_one_reading_for_the_span_and_the_timeline(
        fresh_ring, tmp_path):
    """``CollectiveBackend.activity``: the timeline's sub-activity takes
    the span's two clock readings, for every tensor of the batch."""
    import json

    from horovod_tpu.common.timeline import (
        ACT_MEMCPY_IN_FUSION_BUFFER, Timeline,
    )
    from horovod_tpu.ops.backend import CollectiveBackend
    htrace.arm_spans(True)
    backend = CollectiveBackend()
    backend.timeline = tl = Timeline(str(tmp_path / "t.json"))
    with backend.activity(["a", "b"], ACT_MEMCPY_IN_FUSION_BUFFER) as sp:
        sp.nbytes = 128
    with backend.activity(["a"], ACT_MEMCPY_IN_FUSION_BUFFER, False) as off:
        assert off is htrace.NOOP_SPAN
    tl.shutdown()
    (pack,) = htrace.recent_spans()
    assert pack.name == "hvd.pack" and pack.counts == {"bytes": 128}
    events = [e for e in json.load(open(tmp_path / "t.json"))
              if e["ph"] in "BE"]
    begins = {e["ts"] for e in events if e["ph"] == "B"}
    ends = {e["ts"] for e in events if e["ph"] == "E"}
    assert len(events) == 4
    assert begins == {(pack.start_ns - tl._start_ns) // 1000}
    assert ends == {(pack.end_ns - tl._start_ns) // 1000}
