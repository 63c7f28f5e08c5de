"""The readers of the program's device-side scopes
(``chipbench/scope_readers.py``): over a hand-made trace each op's own
time is counted once, a ``while`` keeps what its body's ops leave, a
table that knows too little of the window gives no value, and without
a trace or a table (a rehearsal, an older commit) every reader gives
``None``. The ten metric files are held to their entries, in the
root's manifest and in the one the next PR would leave: the scopes a
reader asks for are pinned, the cells it is listed for are the least
it is listed for."""

import json

import pytest

from . import _paths    # noqa: F401  (puts chipbench on the path)
from chipbench import harness, scope_readers

pytestmark = pytest.mark.time_limit(60)

MS = 1e6
SPARSE_CELLS = ["glm47flash-injit-1chip", "qwen3next-injit-1chip",
                "lfm2moe-injit-1chip", "ling3flash-injit-1chip"]
LM_CELLS = ["lm-injit-1chip", "lm-injit-4chip", "phi4flash-injit-1chip"] \
    + SPARSE_CELLS
# metric -> (scopes, less, the cells it is listed for at least)
METRICS = {
    "head_loss_ms_per_step": (("lm_head_loss",), None, LM_CELLS),
    "unscoped_ms_per_step": (("",), None, LM_CELLS),
    "attn_outside_kernels_ms_per_step": (
        ("attn", "mla", "diff_attn", "diff_attn.", "gated_attn",
         "normed_attn"), "flash_", LM_CELLS),
    "mlp_ms_per_step": (("mlp", "moe.shared"), None, LM_CELLS),
    "moe_route_ms_per_step": (("moe.route",), None, SPARSE_CELLS),
    "moe_dispatch_combine_ms_per_step": (
        ("moe.dispatch", "moe.combine"), None, SPARSE_CELLS),
    "ssm_outside_kernels_ms_per_step": (("ssm.",), "ssm_scan_",
                                        ["phi4flash-injit-1chip"]),
    "injit_exchange_ms_per_step": (("exchange",), None, ["lm-injit-4chip"]),
    "gdn_outside_kernels_ms_per_step": (("gdn.",), "gdn_",
                                        ["qwen3next-injit-1chip"]),
    "gdn_conv_ms_per_step": (("gdn.conv",), None,
                             ["qwen3next-injit-1chip"]),
}

TABLE = {"while.4": "lm_head_loss", "fusion.20": "lm_head_loss",
         "fusion.21": "moe.combine", "flash_fwd.12": "attn",
         "fusion.7": "attn", "fusion.8": "mla", "fusion.9": "diff_attn.cross",
         "fusion.50": "", "psum.797": "exchange", "fusion.60": "gdn.conv",
         "gdn_fwd.3": "gdn.rule", "fusion.61": "gdn.rule"}


def events(shift=0.0):
    """Two steps' worth on one device: a ``while`` of 100 ms that holds
    two scoped ops of 30 and 50 (its own 20), then ops outside it."""
    out, t = [], shift
    for _ in range(2):
        out += [("while.4", t, 100 * MS),
                ("fusion.20", t + 10 * MS, 30 * MS),
                ("fusion.21", t + 45 * MS, 50 * MS)]
        t += 100 * MS
        for name, ms in (("flash_fwd.12[tpu_custom_call]", 40),
                         ("fusion.7", 7), ("fusion.8", 8), ("fusion.9", 9),
                         ("fusion.50", 5), ("psum.797[all-reduce]", 3),
                         ("fusion.60", 6), ("gdn_fwd.3[tpu_custom_call]", 11),
                         ("fusion.61", 2)):
            out.append((name, t, ms * MS))
            t += ms * MS
    return out, t - shift


def ctx_of(device_events, busy_ns, steps=2):
    return {"steps": steps, "notes": [],
            "trace": {"events": device_events, "busy_s": busy_ns / 1e9}}


@pytest.fixture
def noted(monkeypatch):
    monkeypatch.setattr(scope_readers, "noted_table", lambda: dict(TABLE))


def test_each_ops_own_time_once_and_the_whiles_remainder_once(noted):
    ev, busy = events()
    ctx = ctx_of({"/device:TPU:0": ev}, busy)
    read = lambda *a, **k: scope_readers.scope_ms_per_step(ctx, *a, **k)
    # the while's 20 and its first op's 30, not 100 + 30
    assert read(("lm_head_loss",)) == pytest.approx(50.0)
    assert read(("moe.combine",)) == pytest.approx(50.0)
    assert read(("attn", "mla", "diff_attn", "diff_attn.", "gated_attn")) \
        == pytest.approx(40 + 7 + 8 + 9)
    assert read(("attn", "mla", "diff_attn", "diff_attn."),
                less="flash_") == pytest.approx(7 + 8 + 9)
    assert read(("gdn.",), less="gdn_") == pytest.approx(6 + 2)
    assert read(("gdn.conv",)) == pytest.approx(6.0)
    assert read(("",)) == pytest.approx(5.0)
    assert read(("exchange",)) == pytest.approx(3.0)
    assert read(("ssm.",)) == 0.0           # nothing under it
    # one note, by the first reader, with the whole table
    (note,) = ctx["notes"]
    assert note.startswith("scopes: {")
    table = json.loads(note[len("scopes: "):note.index(" ms a step")])
    assert table["lm_head_loss"] == 50.0 and table[""] == 5.0
    assert sum(table.values()) == pytest.approx(busy / MS / 2)
    assert "knows 100.000%" in note


def test_the_mean_of_the_devices(noted):
    ev, busy = events()
    late, _ = events(shift=3 * MS)
    ctx = ctx_of({"/device:TPU:0": ev, "/device:TPU:1": late}, busy)
    assert scope_readers.scope_ms_per_step(ctx, ("lm_head_loss",)) \
        == pytest.approx(50.0)


def test_a_table_that_knows_under_99_percent_gives_no_value(noted):
    ev, busy = events()
    known = sum(d for _, _, d in ev) - 2 * 80 * MS      # nested: not own
    assert known == pytest.approx(busy)
    for extra_ms, value in ((0.9, 50.0), (1.1, None)):
        stranger = ("fusion.999", busy, extra_ms / 100 * busy / (
            1 - extra_ms / 100))
        ctx = ctx_of({"/device:TPU:0": ev + [stranger]}, busy)
        got = scope_readers.scope_ms_per_step(ctx, ("lm_head_loss",))
        assert got == (pytest.approx(value) if value else None)
        if value is None:
            assert "another executable's" in ctx["notes"][0]
            # asked again: no second note, still no value
            assert scope_readers.scope_ms_per_step(ctx, ("",)) is None
            assert len(ctx["notes"]) == 1
        else:       # what the table does not know is in the unscoped rest
            assert scope_readers.scope_ms_per_step(ctx, ("",)) \
                == pytest.approx(5.0 + stranger[2] / MS / 2)


def test_no_trace_or_no_table_gives_none(monkeypatch):
    ev, busy = events()
    ctx = ctx_of({"/device:TPU:0": ev}, busy)
    # a program that noted nothing (tracing off, the eager cell) ...
    assert scope_readers.noted_table() is None
    assert scope_readers.scope_ms_per_step(ctx, ("lm_head_loss",)) is None
    # ... or that has no such function (an older commit)
    from horovod_tpu import spmd
    monkeypatch.delattr(spmd, "noted_device_scopes")
    assert scope_readers.noted_table() is None
    # a rehearsal has no trace
    monkeypatch.setattr(scope_readers, "noted_table", lambda: dict(TABLE))
    rehearsal = {"steps": 2, "notes": [], "trace": None}
    assert scope_readers.scope_ms_per_step(rehearsal, ("",)) is None
    assert rehearsal["notes"] == []


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_scope_metric_reads_its_scopes_and_stands_by_its_entry(
        name, noted, monkeypatch, manifest):
    scopes, less, cells = METRICS[name]
    reader = harness.load_module("layer_metrics", name)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        "User's jitted step", "ms", "tokens_per_s_chip")
    asked = []
    monkeypatch.setattr(
        scope_readers, "scope_ms_per_step",
        lambda ctx, scopes, less=None: asked.append((scopes, less)) or 1.5)
    assert reader.read({}) == 1.5 and asked == [(scopes, less)]
    entry = {x["name"]: x for x in manifest["per_layer"]}[name]
    listed = entry.pop("workloads")
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "User's jitted step",
        "moves": "tokens_per_s_chip"}
    assert set(cells) <= set(listed) and len(listed) == len(set(listed))
    assert reader.read.__module__ and reader.__doc__
    monkeypatch.undo()
    assert reader.read({"steps": 2, "notes": [], "trace": None}) is None
