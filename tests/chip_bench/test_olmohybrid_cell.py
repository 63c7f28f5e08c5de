"""The cell ``olmohybrid-injit-1chip`` (family ``olmo_hybrid_lm``): its
check passes at the rehearsal's size and fails with bfloat16
parameters, its file holds the published widths and the cut, its counts
are the hand-computed ones, its two new readers read a hand-made trace
and registry, the readers it joins count this shape, and it is in every
list it joined, whoever else is. What is asserted of the manifest is
asserted of the root's and of the one the next PR would leave
(``conftest.py``). (Cold on this sandbox: 37 s, the check's two
programs and the reference at the rehearsal's size.)

How the reference's fit beside the state was read (PR 46): each stage's
backward compiled for a described v5e chip at the cell's size
(``jax.jit(check.StagedGradient._block_bwd(fn)).lower(abstract
arguments).compile().memory_analysis()`` under
``jax.default_matmul_precision("highest")``): a linear layer's 1.94 GB
of temporaries a group of five heads at a time (4.52 GB over all
thirty, which does not fit beside 11.1 GB of parameters, momentum and
gradients), the full layer's 2.16 GB, the head's 0.28 GB."""

import json
import os

import pytest

from . import _paths
from chipbench import check, gdn_flops, harness

M = _paths.manifest()
CELL = "olmohybrid-injit-1chip"
NAME = "olmo-hybrid-7b-l4"
FAMILY = harness.load_module("families", "olmo_hybrid_lm")
with open(os.path.join(_paths.BENCH, "configs", f"{NAME}.json")) as f:
    CONFIG = json.load(f)
SZ = FAMILY.sizes(CONFIG, CONFIG["assumed"]["per_chip_batch"])
NEW_READERS = ("gdn_layout_fill", "post_norm_ms_per_step")
JOINED = ("tokens_per_s_chip", "step_p90_ms", "mfu.lm",
          "device_idle_share.lm", "hbm_need_gb.lm", "head_loss_ms_per_step",
          "unscoped_ms_per_step", "attn_outside_kernels_ms_per_step",
          "mlp_ms_per_step", "gdn_time_share", "gdn_roofline",
          "gdn_outside_kernels_ms_per_step", "gdn_conv_ms_per_step",
          "mla_flash_time_share", "mla_flash_roofline")
LAYOUT = 'hvd_gdn_layout{kind="%s"}'

pytestmark = pytest.mark.time_limit(170)


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


def test_the_rehearsal_keeps_the_ratio_and_stays_off_the_lane_tile():
    small = dict(CONFIG, **CONFIG["rehearse"])
    dk, dv = small["linear_key_head_dim"], small["linear_value_head_dim"]
    assert dv == 2 * dk and 128 % dk and dk % 128
    assert small["linear_num_key_heads"] == small["linear_num_value_heads"]
    assert CONFIG["linear_value_head_dim"] \
        == 2 * CONFIG["linear_key_head_dim"]


def test_the_file_states_the_published_widths_and_the_cut():
    assert CONFIG["source"].endswith(
        "allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert (SZ["d"], SZ["heads"], SZ["head_dim"], SZ["mlp"]) \
        == (3840, 30, 128, 11008)
    assert (SZ["key_heads"], SZ["value_heads"], SZ["key_dim"],
            SZ["value_dim"], SZ["conv"], SZ["neg_eigval"]) \
        == (30, 30, 96, 192, 4, True)
    assert sorted(CONFIG["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 32,
                                   "vocab_size": 100352}
    assert SZ["kept"] == (0, 1, 2, 3) and SZ["vocab"] * 8 == 100352
    assert len(SZ["layer_types"]) == 32
    assert FAMILY.kinds(SZ) == ["delta", "delta", "delta", "attention"]
    assert (FAMILY.delta_layers(SZ), FAMILY.attention_layers(SZ)) == (3, 1)
    assert SZ["seq"] == 8192 and SZ["per_chip_batch"] == 1
    assert CONFIG["deployment"]["parameters_here"] == 928_862_196
    for key in ("sequence_length", "per_chip_batch", "head_dim", "optimizer",
                "gates", "gates_why", "block", "qk_norm", "positions",
                "qkvz_order", "activations"):
        assert key in CONFIG["assumed"], key
    assert any("lane" in d for d in CONFIG["departures"])
    assert "set_from" in CONFIG["check"]


def test_every_number_of_the_catalogs_row_is_in_the_file(manifest):
    """The row's ``config`` as the catalog of public architectures has
    it: every key under the same name, the value its own unless the key
    is in ``reduced``; nested groups whole."""
    types = ["full_attention" if i % 4 == 3 else "linear_attention"
             for i in range(32)]
    row = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": types, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    for key, value in row.items():
        assert key in CONFIG, key
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value
            assert CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"].endswith(f"configs/{NAME}.json")


def test_the_cell_is_in_every_list_it_joins(manifest):
    """In each of ``JOINED`` and ``NEW_READERS``, once; what else lists
    the cell, and which cells stand beside or behind it, is the
    manifest's."""
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "injit-1chip", 1)
    listed = _paths.listed_for(manifest, CELL)
    assert set(JOINED) | set(NEW_READERS) <= set(listed)
    assert len(listed) == len(set(listed))


# -- counts, by hand ---------------------------------------------------------
D, H, HD, HL, DK, DV, TAPS, FF, V, S = \
    3840, 30, 128, 30, 96, 192, 4, 11008, 12544, 8192
KEYS, VALUES = HL * DK, HL * DV
LINEAR_MIXER = (D * (2 * KEYS + 2 * VALUES) + D * 2 * HL
                + TAPS * (2 * KEYS + VALUES) + HL + HL + DV + VALUES * D)
FULL_MIXER = 4 * D * D + 2 * D
SWIGLU = 3 * D * FF


def test_the_configuration_holds_928_9_million_parameters():
    assert (KEYS, VALUES) == (2880, 5760)
    assert LINEAR_MIXER == 88_750_332 and FULL_MIXER == 58_990_080
    assert SWIGLU == 126_812_160
    linear, full = LINEAR_MIXER + SWIGLU + 2 * D, FULL_MIXER + SWIGLU + 2 * D
    assert (linear, full) == (215_570_172, 185_809_920)
    want = 3 * linear + full + 2 * V * D + D
    assert FAMILY.param_count(SZ) == want == 928_862_196
    assert 12 * want == pytest.approx(11.15e9, rel=1e-3)
    assert 16 * want == pytest.approx(14.9e9, rel=3e-3)
    # whole, by the same equations: the "7B" of its name
    whole = 24 * linear + 8 * full + 2 * 100352 * D + D
    assert whole == pytest.approx(7.431e9, rel=1e-3)
    assert FAMILY.param_count(dict(
        SZ, kept=tuple(range(32)), vocab=100352)) == whole
    # two periods do not fit a chip (ISSUE 46 rounds this to 21.0)
    assert 12 * (want + 3 * linear + full) == pytest.approx(21.14e9, rel=1e-3)


def test_a_token_costs_5_51_gflop():
    """6 per matmul parameter a token meets; attention by the causal
    half, the rule by its recurrence at the published 96 x 192 and the
    convolution's taps, three forwards' worth."""
    matmul = 3 * (D * (2 * KEYS + 2 * VALUES) + D * 2 * HL + VALUES * D) \
        + 4 * D * D + 4 * SWIGLU + D * V
    assert FAMILY.matmul_params_per_token(SZ) == matmul
    attention = 3 * H * 2 * 2 * HD * (S + 1) / 2
    rule = 3 * 3 * 7 * HL * DK * DV
    assert gdn_flops.rule_forward_ops(1, 1, HL, DK, DV) == 7 * HL * DK * DV
    conv = 3 * 3 * 2 * TAPS * (2 * KEYS + VALUES)
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(
        6 * matmul + attention + rule + conv, rel=1e-12)
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(5.508e9, rel=1e-3)
    forward = FAMILY.flops_per_sample(SZ) / 3
    # the shares ISSUE 46 sizes the cell by: the head at 5.5% of the
    # matmuls, the full layer's flash calls about 3% of the step
    assert D * V / matmul == pytest.approx(0.055, abs=0.003)
    assert H * 2 * 2 * HD * (S + 1) / 2 / forward \
        == pytest.approx(0.034, abs=0.003)
    assert 4 * SWIGLU * 2 / forward == pytest.approx(0.55, abs=0.01)


# -- the readers ---------------------------------------------------------------

def ctx_of(peak, trace, registry):
    return {"peak": peak, "trace": trace, "registry": registry, "sz": SZ,
            "family": FAMILY, "steps": 1, "notes": []}


def laid(dk, dv, laid_dk, laid_dv):
    return {LAYOUT % "key_dim": dk, LAYOUT % "value_dim": dv,
            LAYOUT % "laid_key_dim": laid_dk,
            LAYOUT % "laid_value_dim": laid_dv}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_has_nothing_to_report_at_a_rehearsal(name, manifest):
    reader = harness.load_module("layer_metrics", name)
    assert reader.read(ctx_of(None, None, laid(12, 24, 128, 128))) is None
    assert reader.read(ctx_of(None, None, {})) is None
    entry = {x["name"]: x for x in manifest["per_layer"]}[name]
    assert (reader.LAYER, reader.UNIT, reader.MOVES) \
        == (entry["layer"], entry["unit"], entry["moves"])
    assert CELL in entry["workloads"]


def test_the_layouts_fill_is_the_gauges():
    """96 x 192 of 128 x 256: nine sixteenths; whole lanes read 100; a
    program without the gauge (the parent's) gives nothing to read."""
    from chipbench import peaks
    peak = peaks.chip_peak("TPU v5 lite")
    read = lambda registry: harness.load_module(
        "layer_metrics", "gdn_layout_fill").read(ctx_of(peak, None, registry))
    assert read(laid(96, 192, 128, 256)) == pytest.approx(56.25)
    assert read(laid(128, 128, 128, 128)) == pytest.approx(100.0)
    assert read({}) is None
    assert read({'hvd_gdn_chunks{kind="chunk_length"}': 128}) is None


def test_the_readers_match_kernels_and_scopes_and_count_this_shape(
        monkeypatch):
    """A trace of one device with the flash kernels, the rule's two,
    the prologue's and the epilogue's and two fusions, and a by-scope
    table for them: each reader takes its own, the readers the cell
    joins count from ``sz`` what a hand counts at this shape, and a
    share stays under 100 where the kernels take longer than their
    least."""
    from chipbench import peaks, scope_readers
    ms = 1e6
    events = [("flash_fwd.3[tpu_custom_call]", 0.0, 5 * ms),
              ("flash_bwd_dq.4[tpu_custom_call]", 5 * ms, 8 * ms),
              ("flash_bwd_dkv.5[tpu_custom_call]", 13 * ms, 7 * ms),
              ("gdn_fwd.7[tpu_custom_call]", 20 * ms, 20 * ms),
              ("gdn_bwd.8[tpu_custom_call]", 40 * ms, 40 * ms),
              ("qkv_prologue_fwd.9[tpu_custom_call]", 80 * ms, 6 * ms),
              ("delta_epilogue_bwd.10[tpu_custom_call]", 86 * ms, 4 * ms),
              ("fusion.11", 90 * ms, 10 * ms),
              ("fusion.12", 100 * ms, 300 * ms)]
    trace = {"events": {"/device:TPU:0": events}, "busy_s": 0.4,
             "window_s": 0.4}
    scopes = {"flash_fwd.3": "normed_attn", "flash_bwd_dq.4": "normed_attn",
              "flash_bwd_dkv.5": "normed_attn", "gdn_fwd.7": "gdn.rule",
              "gdn_bwd.8": "gdn.rule", "qkv_prologue_fwd.9": "gdn.conv",
              "delta_epilogue_bwd.10": "gdn.gate", "fusion.11": "post_norm",
              "fusion.12": "mlp"}
    monkeypatch.setattr(scope_readers, "noted_table", lambda: scopes)
    peak = peaks.chip_peak("TPU v5 lite")
    ctx = ctx_of(peak, trace, laid(96, 192, 128, 256))
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)
    assert read("post_norm_ms_per_step") == pytest.approx(10.0)
    assert read("mlp_ms_per_step") == pytest.approx(300.0)
    assert read("gdn_conv_ms_per_step") == pytest.approx(6.0)
    assert read("gdn_outside_kernels_ms_per_step") == pytest.approx(10.0)
    assert read("attn_outside_kernels_ms_per_step") == pytest.approx(0.0)
    assert read("gdn_layout_fill") == pytest.approx(56.25)
    assert read("gdn_time_share") == pytest.approx(15.0)
    assert read("mla_flash_time_share") == pytest.approx(5.0)
    # the rule at the published 96 x 192, three layers: memory-bound by
    # what the kernels are handed, whatever they lay out
    ops = 3 * 4 * 7 * S * HL * DK * DV
    bytes_ = gdn_flops.rule_bytes_per_step(1, S, HL, HL, DK, DV, 3)
    assert bytes_ == 3 * 760_872_960 and ops == 380_507_258_880
    assert bytes_ / 819e9 > ops / 197e12
    assert read("gdn_roofline") == pytest.approx(
        100 * (bytes_ / 819e9) / 0.06, rel=1e-6)
    assert 0 < read("gdn_roofline") < 100
    # the flash kernels: nine products of the causal half, 30 heads of
    # 128, a key and a value head a query head, one layer
    least = 9 * S * S * HD * H / 197e12
    assert read("mla_flash_roofline") == pytest.approx(
        100 * least / 0.02, rel=1e-6)
    assert 0 < read("mla_flash_roofline") < 100
    # a trace without the kernels (the parent's) gives nothing to read
    ctx["trace"] = {"events": {"/device:TPU:0": events[-2:]}, "busy_s": 0.4,
                    "window_s": 0.4}
    assert read("gdn_roofline") is None and read("gdn_time_share") is None
