"""The cell ``smallthinker-injit-1chip`` (family ``smallthinker_lm``): its
check passes at the rehearsal's size and fails with bfloat16 parameters;
its file holds the published widths and the cut; its counts are the
hand-computed ones (three bands of 4,096 and one causal half, k and v by
key-value head); it is in every list it joined and in its four new ones,
whoever else is; its new readers have nothing to report at a rehearsal
and count a hand-made trace at this shape. What is asserted of the
manifest is asserted of the root's and of the one the next PR would
leave (``conftest.py``). (Cold on this sandbox: 25 s.)"""

import json
import os

import pytest

from . import _paths
from chipbench import check, harness, hybrid_flops, smallthinker_flops

M = _paths.manifest()
CELL = "smallthinker-injit-1chip"
NAME = "smallthinker-21b-a3b-ep4-l4"
FAMILY = harness.load_module("families", "smallthinker_lm")
with open(os.path.join(_paths.BENCH, "configs", f"{NAME}.json")) as f:
    CONFIG = json.load(f)
SZ = FAMILY.sizes(CONFIG, CONFIG["assumed"]["per_chip_batch"])
SCOPE_READERS = {"swa_attn_outside_kernels_ms_per_step": "swa_attn",
                 "nope_attn_outside_kernels_ms_per_step": "nope_attn"}
FLASH_READERS = ("swa_flash_time_share", "swa_flash_roofline")
NEW_READERS = tuple(SCOPE_READERS) + FLASH_READERS
JOINED = ("tokens_per_s_chip", "step_p90_ms", "mfu.lm",
          "device_idle_share.lm", "hbm_need_gb.lm", "moe_grouped_time_share",
          "moe_grouped_roofline", "moe_load_max_over_mean",
          "moe_dropped_share", "head_loss_ms_per_step",
          "unscoped_ms_per_step", "moe_route_ms_per_step",
          "moe_dispatch_combine_ms_per_step", "moe_layer_ms_per_step")

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
    the update is lost wholesale. The rehearsal keeps seven query heads
    over one key-value head, a window (40) shorter than the row (96),
    both kinds of layer, 4 of 16 experts at offset 4 and three
    choices."""
    import jax.numpy as jnp
    spec = harness.resolve_cell(M, CELL, rehearse=True)
    limits = spec["config"]["check"]["limits"]
    seed = 2**31 + 7
    program, got = first_steps(spec, seed)
    sz = program.sz
    assert (sz["heads"], sz["kv_heads"], sz["window"], sz["seq"],
            sz["kept"], sz["experts_held"], sz["experts"],
            sz["expert_offset"], sz["top_k"]) \
        == (7, 1, 40, 96, (0, 1), 4, 16, 4, 3)
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
        "PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    assert (SZ["d"], SZ["heads"], SZ["kv_heads"], SZ["head_dim"],
            SZ["window"], SZ["theta"]) == (2560, 28, 4, 128, 4096, 1.5e6)
    assert (SZ["expert_mlp"], SZ["experts"], SZ["top_k"], SZ["eps"]) \
        == (768, 64, 6, 1e-6)
    assert sorted(CONFIG["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    assert SZ["kept"] == (0, 1, 2, 3)
    assert [CONFIG["sliding_window_layout"][i] for i in SZ["kept"]] \
        == [0, 1, 1, 1] == [CONFIG["rope_layout"][i] for i in SZ["kept"]]
    assert smallthinker_flops.windows(SZ) == [None, 4096, 4096, 4096]
    dep = CONFIG["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["router_width"],
            dep["expert_offset"], dep["chips_sharing_the_vocabulary"]) \
        == (4, 64, 0, 8)
    assert SZ["experts_held"] * 4 == 64 and SZ["vocab"] * 8 == 151936
    assert SZ["seq"] == 16384 == CONFIG["max_position_embeddings"]
    assert SZ["per_chip_batch"] == 2
    assert "PLACEHOLDER" not in json.dumps(CONFIG)
    for key in ("expert_load", "what"):
        assert dep[key], key
    for key in ("router_input", "biases", "rotary_pairing", "window",
                "norms", "routing", "experts", "sequence_length",
                "per_chip_batch", "optimizer", "activations"):
        assert key in CONFIG["assumed"], key
    assert any("secondary experts" in d for d in CONFIG["departures"])
    assert any("balancing loss" in d for d in CONFIG["departures"])
    assert CONFIG["check"]["set_from"] and CONFIG["rehearse"]["check"]


def test_every_number_of_the_catalogs_row_is_in_the_file(manifest):
    """The row's ``config`` as the catalog of public architectures has
    it: every key under the same name, the value its own unless the key
    is in ``reduced``."""
    layout = [int(i % 4 != 0) for i in range(52)]
    row = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": layout, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936}
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
    assert entry["file"] == f"benchmarks/chip/configs/{NAME}.json"


def test_the_cell_is_in_every_list_it_joins(manifest):
    """In each of ``JOINED`` and ``NEW_READERS``, once; what else lists
    the cell, and which cells stand beside or behind it, is the
    manifest's (``test_manifest.py`` holds every list to the rules, and
    ``test_rehearse.py`` every listed reader to reading its cell)."""
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "injit-1chip", 1)
    listed = _paths.listed_for(manifest, CELL)
    assert set(JOINED) | set(NEW_READERS) <= set(listed)
    assert len(listed) == len(set(listed))


# -- counts, by hand ---------------------------------------------------------
D, H, KV, HD, W, E, HELD, K, V, S, B, WIN = \
    2560, 28, 4, 128, 768, 64, 16, 6, 18992, 16384, 2, 4096
ATTN = D * H * HD + 2 * D * KV * HD + H * HD * D
EXPERT = 3 * D * W
BAND = WIN * (WIN + 1) // 2 + (S - WIN) * WIN
HALF = S * (S + 1) // 2


def test_the_configuration_holds_559_290_880_parameters():
    outside = ATTN + 2 * D + D * E
    assert (ATTN, outside, EXPERT) == (20_971_520, 21_140_480, 5_898_240)
    layer = outside + HELD * EXPERT
    assert layer == 115_512_320
    want = 4 * layer + 2 * V * D + D
    assert want == 559_290_880 == FAMILY.param_count(SZ)
    assert 12 * want == pytest.approx(6.71e9, rel=1e-3)
    # whole, by the same equations: the 21BA3B of its name
    whole_layer = outside + 64 * EXPERT
    assert whole_layer == 398_627_840
    whole = 52 * whole_layer + 2 * 151936 * D + D
    assert whole == pytest.approx(21.51e9, rel=1e-3)
    assert whole - 52 * (64 - K) * EXPERT == pytest.approx(3.72e9, rel=1e-3)
    # two periods would be the size at which another configuration
    # recorded "no step"
    assert 12 * (8 * layer + 2 * V * D + D) == pytest.approx(12.26e9,
                                                             rel=1e-3)


def test_a_token_costs_1_83_gflop():
    """6 per matmul parameter a token meets (one and a half routed
    experts a layer by expectation); attention by the scores the masks
    allow: three bands of 4,096 and one causal half, three forwards'
    worth."""
    routed = K * HELD / E
    assert routed == 1.5
    matmul = 4 * (ATTN + D * E + routed * EXPERT) + D * V
    assert FAMILY.matmul_params_per_token(SZ) == matmul == 168_550_400
    assert (BAND, HALF) == (58_722_304, 134_225_920)
    assert smallthinker_flops.needed_scores_per_head(SZ) \
        == 3 * BAND + HALF == 310_392_832
    attention = 3 * H * 2 * 2 * HD * (3 * BAND + HALF) / S
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(
        6 * matmul + attention, rel=1e-12)
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(1.8260e9, rel=1e-4)
    assert FAMILY.samples_per_row(SZ) == S
    # a step: 59.8 TFLOP, 0.30 s at the chip's peak; the head is 29% of
    # the matmuls
    assert B * S * FAMILY.flops_per_sample(SZ) == pytest.approx(
        59.83e12, rel=1e-3)
    assert D * V / matmul == pytest.approx(0.288, abs=1e-3)


def test_the_flash_kernels_need_40_tflop_a_step_k_and_v_by_their_own_heads():
    """Nine products a needed score a query head (18 d); q, o, do, dq a
    query head and k, v, dk, dv a key-value head, each once a kernel
    that touches it, whatever the layer's window."""
    flops = B * H * 18 * HD * (3 * BAND + HALF)
    assert smallthinker_flops.flash_flops_per_step(SZ) == flops \
        == pytest.approx(40.05e12, rel=1e-3)
    q_side = (HD + HD) + (3 * HD) + (HD + HD)
    kv_side = 2 * HD + 2 * HD + 4 * HD
    bytes_ = B * 4 * (H * S * (2 * q_side + 20) + KV * S * 2 * kv_side)
    assert smallthinker_flops.flash_bytes_per_step(SZ) == bytes_ \
        == 7_723_810_816
    # by a query head k and v would count seven times as often
    assert hybrid_flops.flash_bytes_per_step(B, H, H, S, HD, HD, 4) \
        - bytes_ == B * 4 * (H - KV) * S * 2 * kv_side
    assert flops / 197e12 > 20 * bytes_ / 819e9         # compute-bound
    # all four layers full would need 1.73 times the scores
    assert 4 * HALF / (3 * BAND + HALF) == pytest.approx(1.730, abs=1e-3)


# -- the readers ---------------------------------------------------------------

def ctx_of(peak, trace, registry=None):
    return {"peak": peak, "trace": trace, "registry": registry or {},
            "sz": SZ, "family": FAMILY, "steps": 7, "notes": []}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_has_nothing_to_report_at_a_rehearsal(name, manifest):
    reader = harness.load_module("layer_metrics", name)
    assert reader.read(ctx_of(None, None)) is None
    entry = {x["name"]: x for x in manifest["per_layer"]}[name]
    assert (reader.LAYER, reader.UNIT, reader.MOVES) \
        == (entry["layer"], entry["unit"], entry["moves"])
    assert CELL in entry["workloads"]
    assert entry["source"] == "device_trace"
    assert (entry["unit"] == "%") == (name in FLASH_READERS)


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_a_scope_reader_reads_its_kind_of_layer_less_the_kernels(
        name, monkeypatch):
    """A table with both kinds of attention, a flash kernel under each
    and other scopes: the reader's sum is its own scope's events that
    are no ``flash_`` kernel, a step."""
    from chipbench import scope_readers
    from horovod_tpu import spmd
    from horovod_tpu.common import trace as program_trace
    assert {"swa_attn", "nope_attn"} <= set(program_trace.DEVICE_SCOPES)
    table = {"fusion.1": "swa_attn", "fusion.2": "nope_attn",
             "flash_fwd.3": "swa_attn", "flash_bwd_dkv.4": "nope_attn",
             "fusion.5": "moe.route", "fusion.6": "loss",
             "fusion.7": "swa_attn", "fusion.8": "nope_attn"}
    monkeypatch.setattr(scope_readers, "noted_table", lambda: table)
    monkeypatch.setattr(spmd, "scope_of",
                        lambda t, name: t.get(name.split("[", 1)[0]))
    ms, at, events = 1e6, 0.0, []
    lengths = {"fusion.1": 3, "fusion.2": 5, "flash_fwd.3": 40,
               "flash_bwd_dkv.4": 60, "fusion.5": 7, "fusion.6": 11,
               "fusion.7": 13, "fusion.8": 17}
    for event, length in lengths.items():
        suffix = "[tpu_custom_call]" if event.startswith("flash") else ""
        events.append((event + suffix, at, length * ms))
        at += length * ms
    ctx = ctx_of(object(), {"events": {"/device:TPU:0": events},
                            "busy_s": at / 1e9, "window_s": at / 1e9})
    ctx["steps"] = 2
    want = {"swa_attn": 3 + 13, "nope_attn": 5 + 17}[SCOPE_READERS[name]] / 2
    assert harness.load_module("layer_metrics", name).read(ctx) \
        == pytest.approx(want)
    # a program without a table (the parent commit): nothing to read
    monkeypatch.setattr(scope_readers, "noted_table", lambda: None)
    ctx.pop("device_scopes")
    assert harness.load_module("layer_metrics", name).read(ctx) is None


def test_the_flash_readers_count_a_hand_made_trace_at_this_shape():
    """A trace of one device with the three flash kernels, the grouped
    products and another custom call: the flash readers take their own
    by name and count three bands and a half over 28 query and 4
    key-value heads of 128, two rows of 16,384; a share stays under 100
    where the kernels take longer than their least."""
    from chipbench import peaks
    ms = 1e6
    events = [("flash_fwd.3[tpu_custom_call]", 0.0, 90 * ms),
              ("flash_bwd_dq.4[tpu_custom_call]", 90 * ms, 120 * ms),
              ("flash_bwd_dkv.5[tpu_custom_call]", 210 * ms, 190 * ms),
              ("ragged-dot.11", 400 * ms, 100 * ms),
              ("other.2[tpu_custom_call]", 500 * ms, 100 * ms),
              ("fusion.9", 600 * ms, 400 * ms)]
    trace = {"events": {"/device:TPU:0": events}, "busy_s": 1.0,
             "window_s": 1.0}
    ctx = ctx_of(peaks.chip_peak("TPU v5 lite"), trace)
    ctx["steps"] = 1
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)
    assert read("swa_flash_time_share") == pytest.approx(40.0)
    least = 18 * HD * B * H * (3 * BAND + HALF) / 197e12
    assert least == pytest.approx(203.3e-3, rel=1e-3)
    assert read("swa_flash_roofline") == pytest.approx(
        100 * least / 0.4, rel=1e-6)
    assert 0 < read("swa_flash_roofline") < 100
    assert any("swa_flash_roofline: compute-bound" in n
               for n in ctx["notes"])
    # two steps in the same trace: half the time a step
    ctx["steps"] = 2
    assert read("swa_flash_roofline") == pytest.approx(
        100 * 2 * least / 0.4, rel=1e-6)
    # a trace without the kernels gives nothing to read
    ctx["trace"] = dict(trace, events={"/device:TPU:0": events[-3:]})
    assert read("swa_flash_roofline") is None
    assert read("swa_flash_time_share") is None
