"""The cell ``phi4flash-injit-1chip`` (family ``phi4flash_lm``): its
check passes at the rehearsal's size and fails with bfloat16
parameters, its counts are the hand-computed ones (the window's score
count and the scan's operations among them), and its readers have
nothing to report at a rehearsal."""

import json
import os

import pytest

from . import _paths
from chipbench import check, harness, hybrid_flops, ssm_flops

M = _paths.manifest()
CELL = "phi4flash-injit-1chip"
FAMILY = harness.load_module("families", "phi4flash_lm")
with open(os.path.join(_paths.BENCH, "configs",
                       "phi-4-mini-flash-l6.json")) as f:
    CONFIG = json.load(f)
SZ = FAMILY.sizes(CONFIG, CONFIG["assumed"]["per_chip_batch"])
NEW_READERS = ("ssm_scan_time_share", "ssm_scan_roofline",
               "hybrid_flash_time_share", "hybrid_flash_roofline")
CHUNK_GAUGE = 'hvd_ssm_scan_chunks{kind="chunk_length"}'


@pytest.fixture()
def world():
    import horovod_tpu.jax as hvd
    hvd.init()
    yield hvd
    hvd.shutdown()


def first_steps(spec, seed, param_dtype=None):
    program = harness.Program(spec, seed, 1, param_dtype=param_dtype)
    state = program.make_state()
    batch = program.make_batch(0, program.batch_sharding)
    program.compile(state, batch)
    return program, program.first_steps(state, batch)


def test_the_cell_passes_and_bf16_parameters_fail(world):
    """Every parameter in bfloat16 against the reference of the seed:
    the update is lost wholesale."""
    import jax.numpy as jnp
    spec = harness.resolve_cell(M, CELL, rehearse=True)
    limits = spec["config"]["check"]["limits"]
    seed = 2**31 + 7
    program, got = first_steps(spec, seed)
    reference = program.reference()
    sound = check.compare(got, reference, limits)
    assert all(c["ok"] for c in sound.values()), sound
    _, got = first_steps(spec, seed, jnp.bfloat16)
    control = check.compare(got, reference, limits)
    assert not control["update_norm_gap"]["ok"], control
    assert control["update_norm_gap"]["value"] \
        > 5 * limits["update_norm_gap"]


def test_the_file_states_the_published_widths_and_the_cut():
    assert CONFIG["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert (SZ["d"], SZ["heads"], SZ["kv_heads"], SZ["head_dim"], SZ["mlp"],
            SZ["window"], SZ["d_inner"], SZ["d_state"]) \
        == (2560, 40, 20, 64, 10240, 512, 5120, 16)
    assert sorted(CONFIG["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 200064}
    assert SZ["kept"] == (0, 1, 16, 17, 18, 19)
    assert FAMILY.kinds(SZ) == ["mamba", "window", "mamba", "full", "gmu",
                                "cross"]
    assert SZ["vocab"] * 4 == 200064 and SZ["seq"] == 16384
    for key in ("mamba", "differential_pairing", "lambda_init", "memory",
                "sequence_length", "per_chip_batch", "optimizer",
                "activations"):
        assert key in CONFIG["assumed"], key


# -- counts, by hand ---------------------------------------------------------
D, FFN, DI, N, R, TAPS, HD, V, S = 2560, 10240, 5120, 16, 160, 4, 64, 50016, \
    16384
MLP = D * 2 * FFN + FFN * D
NORMS = 4 * D                                   # two LayerNorms with bias
MAMBA = (D * 2 * DI + TAPS * DI + DI + DI * (R + 2 * N) + R * DI + DI
         + DI * N + DI + DI * D)
ATTN = D * (40 + 20 + 20) * HD + 80 * HD + D * D + D + 4 * HD + 2 * HD
CROSS = 2 * (D * D + D) + 4 * HD + 2 * HD
GMU = 2 * D * DI


def test_the_configuration_holds_761_1_million_parameters():
    want = 2 * MAMBA + 2 * ATTN + CROSS + GMU + 6 * (MLP + NORMS) \
        + V * D + 2 * D
    assert FAMILY.param_count(SZ) == want
    assert want == pytest.approx(761.1e6, rel=1e-4)
    # the layers as ISSUE 31 counts them, in millions
    assert MAMBA == pytest.approx(41.2e6, rel=2e-3)
    assert ATTN == pytest.approx(19.7e6, rel=2e-3)
    assert CROSS == pytest.approx(13.1e6, rel=2e-3)
    assert GMU == pytest.approx(26.2e6, rel=2e-3)
    # whole, by the same equations: the published 3.8B
    whole = 9 * (MAMBA + ATTN) + 7 * (GMU + CROSS) + 32 * (MLP + NORMS) \
        + 200064 * D + 2 * D
    assert whole == pytest.approx(3.85e9, rel=2e-3)


def test_a_window_needs_its_band_and_no_more():
    """Row i of a causal map sees i + 1 keys; with a window of 512 at
    most 512 of them."""
    assert hybrid_flops.needed_scores(S) == S * (S + 1) // 2 == 134_225_920
    assert hybrid_flops.needed_scores(S, 512) \
        == sum(min(i + 1, 512) for i in range(S)) == 8_257_792
    assert hybrid_flops.needed_scores(4, 8) == 10       # longer than the row
    assert hybrid_flops.needed_scores(4, 1) == 4        # a row's own key
    assert FAMILY.attention_windows(SZ) == [512, None, None]


def test_the_flash_kernels_need_18_4_tflop_a_step():
    """Two maps a head pair (40 maps), key head 64 and value head 128:
    2 (64 + 128) forward, 2 (128 + 128) in dq, 2 (128 + 256) in dk/dv a
    score."""
    assert hybrid_flops.forward_flops_per_score(64, 128) == 384
    assert hybrid_flops.kernel_flops_per_score(64, 128) \
        == 384 + 512 + 768 == 1664
    # one head size: flops.py's nine products of the causal half
    assert hybrid_flops.kernel_flops_per_score(128, 128) == 18 * 128
    scores = 8_257_792 + 2 * 134_225_920
    got = hybrid_flops.flash_flops_per_step(1, 40, S, 64, 128,
                                            [512, None, None])
    assert got == 40 * 1664 * scores == pytest.approx(18.42e12, rel=1e-3)
    q_side = (64 + 128) + (128 + 128) + (64 + 128)      # fwd, dq, dk/dv
    kv_side = 4 * (64 + 128)
    assert hybrid_flops.flash_bytes_per_step(1, 40, 20, S, 64, 128, 3) \
        == 3 * (40 * S * (q_side * 2 + 20) + 20 * S * kv_side * 2)


def test_the_scans_need_77_8_billion_vector_operations_a_step():
    elements = S * DI * N
    assert ssm_flops.scan_elements(1, S, DI, N) == elements == 1_342_177_280
    assert (ssm_flops.FORWARD_OPS, ssm_flops.BACKWARD_OPS) == (7, 22)
    assert ssm_flops.scan_ops_per_step(1, S, DI, N, 2) \
        == 2 * 29 * elements == pytest.approx(77.85e9, rel=1e-3)
    assert ssm_flops.scan_forward_ops(1, S, DI, N) == 7 * elements
    timed, entering, bc = S * DI * 4, (S // 128) * DI * N * 4, S * 2 * N * 4
    assert ssm_flops.scan_bytes_per_step(1, S, DI, N, 2, 128) \
        == 2 * (8 * timed + 2 * entering + 2 * bc)
    # four vector slots over 8 x 128 lanes at the clock the published
    # matrix peak implies
    clock = 197e12 / (4 * 128 * 128 * 2)
    assert clock == pytest.approx(1.503e9, rel=1e-3)
    assert ssm_flops.vector_peak_ops(197e12) == 4 * 1024 * clock \
        == pytest.approx(6.156e12, rel=1e-3)


def test_a_token_costs_5_35_gflop():
    """6 per matmul parameter a token meets, the head's among them
    (the lookup is a gather); attention by the needed scores, three
    forwards' worth; the scans likewise."""
    mixers = 2 * (D * 2 * DI + DI * (R + 2 * N) + R * DI + DI * D) \
        + 2 * (D * 80 * HD + D * D) + 2 * D * D + GMU
    matmul = mixers + 6 * MLP + D * V
    assert FAMILY.matmul_params_per_token(SZ) == matmul
    attention = 3 * 40 * 384 * (8_257_792 + 2 * 134_225_920) / S
    scan = 3 * 2 * 7 * DI * N
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(
        6 * matmul + attention + scan, rel=1e-12)
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(5.346e9, rel=1e-3)
    # the head's share of a step, as ISSUE 31 sizes the cut
    assert 6 * D * V / FAMILY.flops_per_sample(SZ) \
        == pytest.approx(0.14, abs=0.02)


# -- the readers ---------------------------------------------------------------

def ctx_of(peak, trace, registry):
    return {"peak": peak, "trace": trace, "registry": registry, "sz": SZ,
            "family": FAMILY, "steps": 7, "notes": []}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_has_nothing_to_report_at_a_rehearsal(name, manifest):
    """(Of the root's manifest and of the one the next PR would leave,
    ``conftest.py``: another cell that runs the kernels may be listed
    beside this one.)"""
    reader = harness.load_module("layer_metrics", name)
    assert reader.read(ctx_of(None, None, {CHUNK_GAUGE: 128})) is None
    assert reader.read(ctx_of(None, None, {})) is None
    entry = {x["name"]: x for x in manifest["per_layer"]}[name]
    assert (reader.LAYER, reader.UNIT, reader.MOVES) \
        == (entry["layer"], entry["unit"], entry["moves"])
    assert CELL in entry["workloads"]


def test_the_readers_match_kernels_by_name():
    """A trace of one device with the flash kernels, the scan's two and
    another custom call: each reader takes its own, and a share stays
    under 100 where the kernels take longer than their least."""
    from chipbench import peaks
    ms = 1e6
    events = [("flash_fwd.3[tpu_custom_call]", 0.0, 100 * ms),
              ("flash_bwd_dkv.4[tpu_custom_call]", 100 * ms, 100 * ms),
              ("ssm_scan_fwd.7[tpu_custom_call]", 200 * ms, 10 * ms),
              ("ssm_scan_bwd.8[tpu_custom_call]", 210 * ms, 40 * ms),
              ("other.2[tpu_custom_call]", 250 * ms, 50 * ms),
              ("fusion.9", 300 * ms, 700 * ms)]
    trace = {"events": {"/device:TPU:0": events}, "busy_s": 1.0,
             "window_s": 1.0}
    ctx = ctx_of(peaks.chip_peak("TPU v5 lite"), trace, {CHUNK_GAUGE: 128})
    ctx["steps"] = 1
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)
    assert read("hybrid_flash_time_share") == pytest.approx(20.0)
    assert read("ssm_scan_time_share") == pytest.approx(5.0)
    least = 40 * 1664 * (8_257_792 + 2 * 134_225_920) / 197e12
    assert read("hybrid_flash_roofline") == pytest.approx(
        100 * least / 0.2, rel=1e-6)
    least = 2 * 29 * S * DI * N / (4 * 1024 * 197e12 / (4 * 128 * 128 * 2))
    assert read("ssm_scan_roofline") == pytest.approx(
        100 * least / 0.05, rel=1e-6)
    assert 0 < read("ssm_scan_roofline") < 100
    assert any("vector-unit-bound" in n for n in ctx["notes"])
    # a program that wrote no gauge (the parent's) gives nothing to read
    ctx["registry"] = {}
    assert read("ssm_scan_roofline") is None
    ctx["trace"] = {"events": {"/device:TPU:0": events[-2:]}, "busy_s": 1.0,
                    "window_s": 1.0}
    assert read("hybrid_flash_roofline") is None
    assert read("ssm_scan_time_share") is None
