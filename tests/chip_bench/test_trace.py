"""The reduction from a trace to numbers, on a small trace worked by
hand and on a cut of a trace recorded on the chip."""

import copy
import json
import os

import pytest

from . import _paths
from chipbench import trace_reduce as tr

US = 1_000.0   # nanoseconds


def trace(ops, spans):
    ops, spans = copy.deepcopy(ops), copy.deepcopy(spans)
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step", 0, 1]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": spans}]}]}


# A window of 100 us: a while of 40 us holding two fusions of 10 us, an
# idle gap of 20 us under bench.exchange, a fusion of 30 us that runs
# past the window's end, a gap of 2 us, and an op before the window.
OPS = [["early.1", -50 * US, 10 * US],
       ["while.1", 0, 40 * US],
       ["fusion.1", 5 * US, 10 * US], ["fusion.2", 20 * US, 10 * US],
       ["flash_fwd", 60 * US, 8 * US],
       ["fusion.3", 70 * US, 50 * US]]
SPANS = [["bench.window", 0, 100 * US],
         ["bench.step", 0, 100 * US],
         ["bench.backward", 0, 38 * US],
         ["bench.exchange", 38 * US, 30 * US],
         ["$python frame", 0, 100 * US]]


def test_busy_is_the_union_clipped_to_the_window():
    r = tr.reduce_trace(trace(OPS, SPANS))
    assert r["window_s"] == pytest.approx(100e-6)
    # 40 (while and its body) + 8 (flash) + 30 (fusion.3 inside) = 78 us
    assert r["busy_s"] == pytest.approx(78e-6)
    assert r["devices"] == 1


def test_nested_ops_are_taken_out_of_their_parent():
    own = tr.self_times([tuple(e) for e in OPS[1:4]])
    assert own == {"while.1": 20 * US, "fusion.1": 10 * US,
                   "fusion.2": 10 * US}
    r = tr.reduce_trace(trace(OPS, SPANS))
    assert r["device_ops"][0] == ["fusion.3", pytest.approx(30e-6)]
    assert dict(map(tuple, r["device_ops"]))["while.1"] \
        == pytest.approx(20e-6)


def test_gaps_go_to_the_innermost_span_open_on_the_host():
    r = tr.reduce_trace(trace(OPS, SPANS))
    gaps = dict(map(tuple, r["idle_gaps"]))
    # 40..60 us lies under bench.exchange (inside bench.step); 68..70 us
    # is a launch gap
    assert gaps == {"bench.exchange": pytest.approx(20e-6),
                    tr.SHORT_GAPS: pytest.approx(2e-6)}


def test_a_gap_under_no_span_says_so():
    spans = [["bench.window", 0, 100 * US]]
    r = tr.reduce_trace(trace(OPS, spans))
    assert dict(map(tuple, r["idle_gaps"]))[tr.NO_SPAN] \
        == pytest.approx(20e-6)


def test_kernel_time_by_name():
    r = tr.reduce_trace(trace(OPS, SPANS))
    events = r["events"]["/device:TPU:0"]
    assert tr.time_of(events, lambda n: n.startswith("flash")) == 8 * US
    assert tr.time_of(events, lambda n: n.startswith("fusion")) == 50 * US


def test_busy_is_averaged_over_devices():
    t = trace(OPS, SPANS)
    t["planes"].append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["fusion.9", 0, 22 * US]]}]})
    r = tr.reduce_trace(t)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((78e-6 + 22e-6) / 2)


@pytest.mark.parametrize("broken,match", [
    (lambda t: t["planes"][1]["lines"][0]["events"].pop(0), "bench.window"),
    (lambda t: t["planes"][0]["lines"].pop(1), "XLA Ops"),
    (lambda t: t["planes"].pop(0), "no device plane"),
])
def test_a_trace_without_what_is_read_is_an_error(broken, match):
    t = trace(OPS, SPANS)
    broken(t)
    with pytest.raises(ValueError, match=match):
        tr.reduce_trace(t)


RECORDED = os.path.join(_paths.BENCH, "testdata", "trace_small.json")


def test_recorded_chip_trace_reduces_to_its_recorded_numbers():
    """A cut of a trace the chip gave (``cut_trace.py``): the reduction
    of its events gives the numbers written beside them when it was
    cut, so that a change to the reduction shows."""
    with open(RECORDED) as f:
        rec = json.load(f)
    r = tr.reduce_trace(rec["trace"])
    assert r["window_s"] == pytest.approx(rec["expect"]["window_s"])
    assert r["busy_s"] == pytest.approx(rec["expect"]["busy_s"])
    assert r["device_ops"][0][0] == rec["expect"]["top_op"]
    assert 0 < r["busy_s"] <= r["window_s"]
    # one eager step: the device waits longest while the host is in
    # hvd.allreduce_gradients, and the step's compute is 98 ms
    assert r["idle_gaps"][0][0] == "bench.exchange"
    assert r["busy_s"] == pytest.approx(0.098, abs=0.001)
    events = r["events"]["/device:TPU:0"]
    fusions = tr.time_of(events, lambda n: "fusion" in n)
    assert 0.5 * r["busy_s"] < fusions / 1e9 <= r["busy_s"]


# -- collective_time_share ---------------------------------------------------

def collective_share(reduced):
    from chipbench import harness
    reader = harness.load_module("layer_metrics", "collective_time_share")
    return reader.read({"trace": reduced})


def test_collective_time_share_is_a_devices_mean_self_time():
    """Two devices over a window of 100 us. The first: an all-reduce of
    12 us named for JAX's psum inside a ``while`` (taken out of it), an ``all-reduce-start``
    of 1 us and its ``-done`` of 6 us, a reduce-scatter of 4 us that
    holds a fusion of 1 us (taken out), an all-gather cut to 3 us by the
    window's end; 12 + 1 + 6 + 3 + 3 = 25 us. The second has none: 0.
    The mean is 12.5 us of 100."""
    t = trace(
        [["while.1", 0, 40 * US], ["fusion.1", 2 * US, 10 * US],
         ["psum.3[all-reduce]", 20 * US, 12 * US],
         ["all-reduce-start.1", 45 * US, 1 * US],
         ["fusion.2", 46 * US, 10 * US],
         ["all-reduce-done.1", 56 * US, 6 * US],
         ["reduce-scatter.2", 70 * US, 4 * US], ["fusion.3", 71 * US, 1 * US],
         ["not-all-reduce.1", 80 * US, 5 * US],
         ["all-gather.7", 97 * US, 9 * US]],
        [["bench.window", 0, 100 * US]])
    t["planes"].append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["fusion.9", 0, 22 * US]]}]})
    r = tr.reduce_trace(t)
    assert r["devices"] == 2
    assert collective_share(r) == pytest.approx(12.5)


def test_collective_time_share_of_one_chip_is_zero_and_of_no_trace_none():
    with open(RECORDED) as f:
        r = tr.reduce_trace(json.load(f)["trace"])
    share = collective_share(r)
    assert share == 0.0 and share is not None
    assert collective_share(None) is None


def test_a_collective_keeps_its_opcode_where_its_name_does_not_say_it():
    psum = ('%psum.797 = f32[2048,50304]{1,0:T(8,128)} all-reduce('
            '%get-tuple-element.517), channel_id=1, replica_groups={{0,1,2,3}}')
    combined = ('%all-reduce.58 = (f32[2048,8192]{1,0}, /*index=1*/f32[16]{0}) '
                'all-reduce(%bitcast_convert_fusion.16, %custom-call.247)')
    user = ('%fusion.7 = f32[8]{0} fusion(%all-reduce.58, %psum.797), '
            'kind=kLoop, calls=%fused_computation.3')
    done = '%ar-done.2 = f32[8]{0} all-reduce-done(%all-reduce-start.2)'
    assert tr.short_name(psum) == "psum.797[all-reduce]"
    assert tr.short_name(combined) == "all-reduce.58"
    assert tr.short_name(user) == "fusion.7"
    assert tr.short_name(done) == "ar-done.2[all-reduce-done]"
    assert tr.short_name("%custom-call.7 = f32[8]{0} custom-call(%x), "
                         'custom_call_target="tpu_custom_call"') \
        == "custom-call.7[tpu_custom_call]"
    for name in ("psum.797[all-reduce]", "all-reduce.58", "all-gather.1",
                 "ar-done.2[all-reduce-done]", "reduce-scatter-start.4",
                 "collective-permute-done.9"):
        assert tr.is_collective(name), name
    for name in ("fusion.7", "custom-call.7[tpu_custom_call]",
                 "not-all-reduce.1", "while.1", "x[7]"):
        assert not tr.is_collective(name), name
