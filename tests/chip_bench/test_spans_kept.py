"""The readers of the program's own spans: the entries kept in
``kept/eager-spans.json`` (six of them in the manifest too since PR 45;
``pack_unpack_ms_per_step`` has nothing to read at one rank) are well
formed as ``test_manifest.py`` holds the others to be, the eager cell
rehearses traced from a manifest with both kept files merged in and
the span readers give values that a manifest without them does not,
and ``program_spans.idle_under`` splits a hand-made trace as it
should."""

import copy
import re

import pytest

from . import _paths
from .test_manifest import NAME, SOURCES, UNIT, reader_constants
from .test_rehearse import check_rehearsal, run

CELL = "resnet50-eager-1rank"
SPANS_KEPT = _paths.kept("eager-spans.json")
NEW = {m["name"]: m for m in SPANS_KEPT["per_layer"]}
# the readers that find something to read on the CPU, where nothing is
# profiled and LocalBackend packs nothing
REHEARSED = ["enqueue_ms_per_step", "queue_wait_ms_per_step",
             "cycle_ms_per_step", "complete_ms_per_step",
             "sync_wait_ms_per_step"]


def manifest_with_both(m=None) -> dict:
    return _paths.merge_kept(_paths.manifest_with_kept(m), SPANS_KEPT)


def without_the_spans(m: dict) -> dict:
    """``m`` as it was before the seven came in: their entries out."""
    m["per_layer"] = [x for x in m["per_layer"] if x["name"] not in NEW]
    return m


@pytest.mark.parametrize("name", sorted(NEW))
def test_kept_span_metric_and_its_reader_file(name, manifest):
    m = NEW[name]           # the kept file's own entry, not a manifest's
    assert set(m) == {"name", "unit", "better", "source", "layer",
                      "moves", "workloads"}
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["better"] == "lower" and m["source"] in SOURCES
    assert m["workloads"] == [CELL]
    consts = reader_constants(name, manifest)
    assert (consts["LAYER"], consts["UNIT"], consts["MOVES"]) == (
        m["layer"], m["unit"], m["moves"])
    both = manifest_with_both(copy.deepcopy(manifest))
    assert m["moves"] in [e["name"] for e in both["end_to_end"]]
    assert m["layer"] in {x["layer"] for x in _paths.manifest_with_kept(
        without_the_spans(manifest))["per_layer"]}
    names = [x["name"] for x in both["end_to_end"] + both["per_layer"]]
    assert names.count(name) == 1
    mine = {x["name"]: x for x in both["per_layer"]}[name]
    assert CELL in mine["workloads"]
    assert _paths.but_workloads(mine) == _paths.but_workloads(m)


def test_state_broadcast_s_is_listed_for_every_cell(manifest):
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "state_broadcast_s"]
    assert entry == {"name": "state_broadcast_s", "unit": "s",
                     "better": "lower", "source": "program_span",
                     "layer": "Launcher and start-up", "moves": "setup_s"}
    assert not SPANS_KEPT["workloads"] and not SPANS_KEPT["end_to_end"]


def test_eager_cell_rehearses_traced_with_the_span_readers(tmp_path):
    """Through the command, from a root whose manifest has both kept
    files' entries: the span readers give values there (the count the
    rehearsal prints), and a root whose manifest is without
    ``eager-spans.json``'s seven (taken out, since the manifest has had
    six of them from PR 45 on) gets five values fewer."""
    both = manifest_with_both()
    _paths.checkout_with(both, tmp_path)
    out = run(["--workload", CELL, "--seed", str(2**31 + 24), "--seconds",
               "1", "--trace", "1", "--rehearse"], cwd=tmp_path)
    check_rehearsal(out, CELL, root=tmp_path)
    for name in NEW:
        assert f'"{name}"' not in out.stdout
    listed, gave = map(int, re.search(
        r"per-layer readers (\d+) listed for the cell, (\d+) gave a value",
        out.stdout).groups())
    assert listed == len([m for m in both["per_layer"]
                          if CELL in m.get("workloads", [CELL])])
    old = without_the_spans(_paths.manifest_with_kept())
    assert len(both["per_layer"]) - len(old["per_layer"]) == len(NEW)
    _paths.checkout_with(old, tmp_path / "old")
    before = run(["--workload", CELL, "--seed", str(2**31 + 24),
                  "--seconds", "1", "--trace", "1", "--rehearse"],
                 cwd=tmp_path / "old")
    gave_before = int(re.search(r"(\d+) gave a value",
                                before.stdout).group(1))
    # state_broadcast_s is in the manifest itself: both runs read it
    assert gave - gave_before == len(REHEARSED)


def make_ctx(spans, events, steps=2):
    return {"steps": steps, "notes": [], "registry": {},
            "trace": {"spans": spans, "events": {"/device:TPU:0": events}}}


def test_idle_under_splits_a_hand_made_trace(monkeypatch):
    """Two steps of 100 us on the trace's clock; the ring's clock runs
    1e12 ns ahead. Device idle inside hvd.allreduce_gradients goes to
    the innermost span of each thread at the gap's middle."""
    from chipbench import program_spans as ps
    from horovod_tpu.common.trace import SpanRecord
    ahead = 10**12
    us = 1000
    spans = [("bench.window", 0.0, 200.0 * us)]
    ring = []

    def rec(name, start_us, end_us, thread, i=[0]):
        i[0] += 1
        return SpanRecord(name, ahead + int(start_us * us),
                          ahead + int(end_us * us), thread, 0, 0, {}, i[0])

    events = []
    for step in (0, 100):
        spans.append(("bench.exchange", (step + 9.0) * us, 80.0 * us))
        # the program's span opens 1 us inside the harness's
        ring.append(rec("hvd.allreduce_gradients", step + 10, step + 88,
                        "MainThread"))
        ring.append(rec("hvd.enqueue", step + 10, step + 20, "MainThread"))
        ring.append(rec("hvd.synchronize", step + 20, step + 88,
                        "MainThread"))
        ring.append(rec("hvd.cycle", step + 30, step + 60, "hvd-background"))
        ring.append(rec("hvd.execute", step + 40, step + 50,
                        "hvd-background"))
        # busy: the backward until 25, the apply from 90; idle 25-90 but
        # for a 2 us blip of device work at 44-46 and at 70-72
        events += [("fusion.1", float(step * us), 25.0 * us),
                   ("fusion.2", (step + 44.0) * us, 2.0 * us),
                   ("fusion.3", (step + 70.0) * us, 2.0 * us),
                   ("fusion.4", (step + 90.0) * us, 10.0 * us)]
    ring.append(rec("hvd.allreduce_gradients", 500, 600, "MainThread"))
    monkeypatch.setattr(ps, "ring", lambda: ring)
    ctx = make_ctx(spans, events)
    assert ps.ring_offset_ns(ctx, ring) == ahead + 1 * us
    table = ps.idle_under(ctx, "hvd.allreduce_gradients")
    sync = "hvd.synchronize"
    # gaps a step: 25-44 (middle 34.5: cycle), 46-70 (middle 58: cycle),
    # 72-90 (middle 81: no background span)
    assert table == {
        (sync, "hvd.cycle"): 2 * (19.0 + 24.0) * us,
        (sync, ps.NO_BACKGROUND_SPAN): 2 * 18.0 * us}
    # the reader: milliseconds a step, and the table as a note
    from chipbench import harness
    ctx["registry"] = {
        f'hvd_span_seconds{{span="{name}"}}': {"sum": s, "count": 2}
        for name, s in (("hvd.allreduce_gradients", 156e-6),
                        ("hvd.enqueue", 20e-6), ("hvd.synchronize", 136e-6))}
    reader = harness.load_module("layer_metrics", "exchange_exposed_ms")
    assert reader.read(ctx) == pytest.approx(0.061)
    (note,) = ctx["notes"]
    assert "hvd.allreduce_gradients is 0.078 ms a step" in note
    assert "hvd.synchronize | hvd.cycle = 0.043; hvd.synchronize | " \
        "(background: no span open) = 0.018" in note
    # the clocks disagree from step to step by more than the limit: none
    ring[0] = ring[0]._replace(start_ns=ring[0].start_ns + 10**6)
    assert ps.ring_offset_ns(ctx, ring) is None
    assert ps.idle_under(ctx, "hvd.allreduce_gradients") is None
    assert "not aligned" in ctx["notes"][-1]


def test_span_readers_read_the_registry_and_the_ring(monkeypatch):
    from chipbench import harness, program_spans as ps
    from horovod_tpu.common.trace import SpanRecord
    ctx = make_ctx([], [], steps=4)
    ctx["trace"] = None
    ctx["registry"] = {
        'hvd_span_seconds{span="hvd.enqueue"}': {"sum": 0.008, "count": 4},
        'hvd_span_seconds{span="hvd.pack"}': {"sum": 0.0, "count": 0}}
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)
    assert read("enqueue_ms_per_step") == pytest.approx(2.0)
    assert read("pack_unpack_ms_per_step") is None
    assert read("cycle_ms_per_step") is None
    assert read("exchange_exposed_ms") is None
    first = SpanRecord("hvd.broadcast_parameters", 5, 5 + 3 * 10**8,
                       "MainThread", 0, 0, {}, 1)
    later = first._replace(end_ns=99 * 10**9, id=2)
    monkeypatch.setattr(ps, "ring", lambda: [first, later])
    assert read("state_broadcast_s") == pytest.approx(0.3)
    monkeypatch.setattr(ps, "ring", lambda: None)   # dropped, or none
    assert read("state_broadcast_s") is None
