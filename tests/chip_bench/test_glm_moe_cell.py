"""The cell ``glm47flash-injit-1chip`` (family ``glm_moe_lm``): its
check passes at the rehearsal's size and fails with bfloat16
parameters, its counts are the hand-computed ones, and its readers
have nothing to report at a rehearsal."""

import json
import os

import pytest

from . import _paths
from chipbench import check, flops, harness, moe_flops

M = _paths.manifest()
CELL = "glm47flash-injit-1chip"
FAMILY = harness.load_module("families", "glm_moe_lm")
with open(os.path.join(_paths.BENCH, "configs",
                       "glm-4.7-flash-ep8-l5.json")) as f:
    CONFIG = json.load(f)
SZ = FAMILY.sizes(CONFIG, CONFIG["assumed"]["per_chip_batch"])
NEW_READERS = ("moe_grouped_time_share", "moe_grouped_roofline",
               "mla_flash_time_share", "mla_flash_roofline",
               "moe_load_max_over_mean", "moe_dropped_share")


@pytest.fixture()
def world():
    import horovod_tpu.jax as hvd
    hvd.init()
    yield hvd
    hvd.shutdown()


def first_steps(spec, seed, param_dtype=None, control_leaves=""):
    program = harness.Program(spec, seed, 1, param_dtype=param_dtype,
                              control_leaves=control_leaves)
    state = program.make_state()
    batch = program.make_batch(0, program.batch_sharding)
    program.compile(state, batch)
    return program, program.first_steps(state, batch)


def test_the_cell_passes_and_bf16_parameters_fail(world):
    """Every parameter in bfloat16, and the experts' leaves alone,
    against the one reference of the seed."""
    import jax.numpy as jnp
    spec = harness.resolve_cell(M, CELL, rehearse=True)
    limits = spec["config"]["check"]["limits"]
    seed = 2**31 + 7
    program, got = first_steps(spec, seed)
    reference = program.reference()
    sound = check.compare(got, reference, limits)
    assert all(c["ok"] for c in sound.values()), sound
    for only in ("", "experts"):
        _, got = first_steps(spec, seed, jnp.bfloat16, only)
        control = check.compare(got, reference, limits)
        assert not control["update_norm_gap"]["ok"], (only, control)


# -- counts, by hand ---------------------------------------------------------
D, HEADS, MLP, WIDTH, VOCAB, SEQ = 2048, 20, 10240, 1536, 19360, 4096
ATTN = (D * 768 + 768 * HEADS * 256          # q through its latent
        + D * (512 + 64) + 512 * HEADS * (192 + 256)   # k and v through theirs
        + HEADS * 256 * D)                   # out
EXPERT = 3 * D * WIDTH
NORMS = 2 * D + 768 + 512                    # a block's four norm scales
EXPERT_BLOCK = ATTN + NORMS + D * 64 + 64 + 8 * EXPERT + EXPERT
DENSE_BLOCK = ATTN + NORMS + 3 * D * MLP


def test_the_configuration_holds_706_5_million_parameters():
    assert ATTN == 21_757_952 and EXPERT == 9_437_184
    mtp = 2 * D * D + EXPERT_BLOCK + 3 * D
    total = DENSE_BLOCK + 4 * EXPERT_BLOCK + mtp + 2 * VOCAB * D + D
    assert FAMILY.param_count(SZ) == total == 706_518_848
    assert 12 * total / 1e9 == pytest.approx(8.478, abs=1e-3)   # GB of state


def test_a_token_costs_3_63_gflop_by_the_palm_count():
    """6 per matmul parameter a token meets, routed experts by their
    expectation of 4 x 8 / 64 = half an expert a layer, the head twice;
    12 L S (20 x 256) for six attention layers."""
    matmul = (6 * ATTN + 3 * D * MLP + 5 * (EXPERT + D * 64 + 0.5 * EXPERT)
              + 2 * D * D + 2 * D * VOCAB)
    assert FAMILY.matmul_params_per_token(SZ) == matmul == 352_583_680
    attention = 12 * 6 * SEQ * HEADS * 256
    assert FAMILY.flops_per_sample(SZ) == 6 * matmul + attention
    assert 6 * matmul / 1e9 == pytest.approx(2.1155, abs=1e-4)
    assert attention / 1e9 == pytest.approx(1.50995, abs=1e-5)
    assert FAMILY.attention_layers(SZ) == 6 and FAMILY.expert_layers(SZ) == 5


def test_the_grouped_products_counts():
    """8,192 assignments a layer by expectation, 40,960 a step over five
    layers; 12 products a layer (3 forward, 6 backward, 3 recomputed)."""
    rows = 5 * moe_flops.expected_rows_per_layer(4 * SEQ, 4, 8, 64)
    assert rows == 40_960
    assert moe_flops.PRODUCTS_PER_LAYER == 12
    assert moe_flops.grouped_flops(rows, D, WIDTH, 12) \
        == 2 * 40_960 * D * WIDTH * 12 == pytest.approx(3.0924e12, rel=1e-4)
    assert moe_flops.grouped_bytes(rows, 5, 8, D, WIDTH, 12) \
        == 2 * 12 * (40_960 * (D + WIDTH) + 5 * 8 * D * WIDTH)
    # a row that reached no held expert multiplies nothing
    assert moe_flops.grouped_flops(0, D, WIDTH, 12) == 0


def test_the_flash_kernels_need_18_55_tflop_a_step_at_head_size_256():
    shape = (4, HEADS, SEQ, 256, 6)
    assert flops.flash_flops_per_step(*shape) \
        == 9 * SEQ * SEQ * 256 * 4 * HEADS * 6 \
        == pytest.approx(18.554e12, rel=1e-4)
    assert flops.flash_bytes_per_step(*shape) \
        == (15 * SEQ * 256 * 2 + 5 * SEQ * 4) * 4 * HEADS * 6


# -- the readers ---------------------------------------------------------------

def ctx_of(peak, trace, registry):
    return {"peak": peak, "trace": trace, "registry": registry, "sz": SZ,
            "family": FAMILY, "steps": 7, "notes": []}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_has_nothing_to_report_at_a_rehearsal(name):
    """No peak and no trace, though the program's counters are there."""
    registry = {'hvd_moe_assignments_total{held="1"}': 40_960.0 * 7,
                "hvd_moe_steps_total": 7.0, "hvd_moe_dropped_total": 0.0,
                "hvd_moe_expert_load_max_over_mean": 1.07}
    reader = harness.load_module("layer_metrics", name)
    assert reader.read(ctx_of(None, None, registry)) is None
    assert reader.read(ctx_of(None, None, {})) is None


def test_the_readers_match_kernels_by_name_and_read_the_counters():
    """A trace of one device with a flash kernel, a grouped product and
    another custom call: each reader takes its own."""
    from chipbench import peaks
    ms = 1e6
    events = [("flash_fwd.3[tpu_custom_call]", 0.0, 100 * ms),
              ("ragged-dot-none.7[tpu_custom_call]", 100 * ms, 20 * ms),
              ("ragged-dot-metadata.1[tpu_custom_call]", 120 * ms, 1 * ms),
              ("other.2[tpu_custom_call]", 121 * ms, 50 * ms),
              ("fusion.9", 171 * ms, 29 * ms)]
    trace = {"events": {"/device:TPU:0": events}, "busy_s": 0.2,
             "window_s": 0.2}
    registry = {'hvd_moe_assignments_total{held="1"}': 40_960.0 * 2,
                "hvd_moe_steps_total": 2.0, "hvd_moe_dropped_total": 0.0,
                "hvd_moe_expert_load_max_over_mean": 1.07}
    ctx = ctx_of(peaks.chip_peak("TPU v5 lite"), trace, registry)
    ctx["steps"] = 1
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)
    assert read("mla_flash_time_share") == pytest.approx(50.0)
    assert read("moe_grouped_time_share") == pytest.approx(10.5)
    least = 18.554e12 / 197e12
    assert read("mla_flash_roofline") == pytest.approx(
        100 * least / 0.1, rel=1e-3)
    least = 2 * 40_960 * D * WIDTH * 12 / 197e12
    assert read("moe_grouped_roofline") == pytest.approx(
        100 * least / 0.021, rel=1e-3)
    assert read("moe_load_max_over_mean") == 1.07
    assert read("moe_dropped_share") == 0.0
    # on the parent the program has no such counters: nothing to read
    ctx["registry"] = {}
    assert read("moe_grouped_roofline") is None
    assert read("moe_load_max_over_mean") is None
    assert read("moe_dropped_share") is None
