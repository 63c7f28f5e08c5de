"""The cell ``ling3flash-injit-1chip`` (family ``ling3flash_lm``): its
check passes at the rehearsal's size, fails with bfloat16 parameters and
comes out not correct with its step broken; its file holds the
published widths and the cut; its counts are the hand-computed ones; it
is in every list it joined (PR 37's scope metrics among them since
PR 45) and in its five new ones, whoever else is; its new readers have
nothing to report at a rehearsal and count a hand-made trace at this
shape, q and k at 192 and v and o at 128. What is asserted of the
manifest is asserted of the root's and of the one the next PR would
leave (``conftest.py``). (Cold on this sandbox: 45 s.)"""

import argparse
import json
import os
import time

import pytest

from . import _paths
from chipbench import check, harness, hybrid_flops, kda_flops

M = _paths.manifest()
CELL = "ling3flash-injit-1chip"
NAME = "ling-3.0-flash-ep64-l7"
FAMILY = harness.load_module("families", "ling3flash_lm")
with open(os.path.join(_paths.BENCH, "configs", f"{NAME}.json")) as f:
    CONFIG = json.load(f)
SZ = FAMILY.sizes(CONFIG, CONFIG["assumed"]["per_chip_batch"])
KERNEL_READERS = ("kda_time_share", "kda_roofline",
                  "latent_flash_time_share", "latent_flash_roofline")
NEW_READERS = KERNEL_READERS + ("kda_outside_kernels_ms_per_step",)
JOINED = ("tokens_per_s_chip", "step_p90_ms", "mfu.lm",
          "device_idle_share.lm", "hbm_need_gb.lm", "moe_grouped_time_share",
          "moe_grouped_roofline", "moe_load_max_over_mean",
          "moe_dropped_share",
          "head_loss_ms_per_step", "unscoped_ms_per_step",
          "attn_outside_kernels_ms_per_step", "mlp_ms_per_step",
          "moe_route_ms_per_step", "moe_dispatch_combine_ms_per_step")

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
        > 2 * limits["update_norm_gap"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    """The rest of the run as it is, the timed path broken underneath:
    the step computes its loss and hands back the state it was given."""
    real = harness.Program.step

    def stuck(self, state, batch, stop=0.0):
        import jax
        kept = jax.tree_util.tree_map(lambda x: x + 0, state)
        _, loss, stop = real(self, state, batch, stop)
        return kept, loss, stop

    monkeypatch.setattr(harness.Program, "step", stuck)
    args = argparse.Namespace(
        workload=CELL, seed=2**31 + 3, seconds=0.5, trace=0, rehearse=True,
        t0=time.time(), launched=None)
    result = harness.run_rank(args, M)
    assert result["correct"] is False
    assert not result["checks"]["update_norm_gap"]["ok"]
    assert not result["checks"]["window_loss_falls"]["ok"]
    assert result["checks"]["replay_loss_gap"]["ok"]


def test_the_file_states_the_published_widths_and_the_cut():
    assert CONFIG["source"].endswith(
        "inclusionAI/Ling-3.0-flash-VL/blob/main/config.json")
    assert (SZ["d"], SZ["heads"], SZ["kda_dim"], SZ["conv"], SZ["lower"],
            SZ["mlp"]) == (2560, 32, 128, 4, -5.0, 6144)
    assert (SZ["kv_rank"], SZ["nope"], SZ["rope"], SZ["v_dim"], SZ["theta"],
            SZ["eps"]) == (512, 128, 64, 128, 6e6, 1e-6)
    assert (SZ["expert_mlp"], SZ["shared_mlp"], SZ["experts"], SZ["top_k"],
            SZ["groups"], SZ["top_groups"], SZ["scale"]) \
        == (768, 768, 512, 8, 8, 4, 2.5)
    assert sorted(CONFIG["reduced"]) == ["num_experts", "num_hidden_layers",
                                         "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 42,
                                   "num_experts": 512, "vocab_size": 157184}
    assert SZ["kept"] == (1, 2, 3, 4, 5, 6, 7) and SZ["group_size"] == 6
    assert (FAMILY.kda_layers(SZ), FAMILY.attention_layers(SZ),
            FAMILY.expert_layers(SZ)) == (6, 1, 6)
    # the clamp is off in every kept layer
    assert not any(CONFIG["expert_swiglu_limit_list"][i]
                   or CONFIG["share_expert_swiglu_limit_list"][i]
                   for i in SZ["kept"])
    dep = CONFIG["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["router_width"],
            dep["expert_offset"]) == (64, 512, 0)
    assert SZ["experts_held"] * 64 == 512 and SZ["vocab"] * 8 == 157184
    assert SZ["seq"] == 16384 and SZ["per_chip_batch"] == 1
    assert "PLACEHOLDER" not in json.dumps(CONFIG)
    for key in ("expert_load", "what"):
        assert dep[key], key
    for key in ("gates", "gates_why", "safe_gate", "output_gates", "qk_norm",
                "rotary_pairing", "projections", "sequence_length",
                "per_chip_batch", "optimizer", "activations",
                "row_tier_headroom", "row_tier_headroom_why"):
        assert key in CONFIG["assumed"], key
    assert SZ["row_tier_headroom"] == 8.0 \
        == FAMILY.build_model(SZ).cfg.row_tier_headroom
    assert CONFIG["assumed"]["gates"] == {"a_log_init": 0.0,
                                          "dt_bias_init": -4.6}
    for word in ("vision tower", "multi-token", "clamp", "correction bias"):
        assert any(word in d for d in CONFIG["departures"]), word
    assert CONFIG["check"]["set_from"] and CONFIG["rehearse"]["check"]


def test_the_cells_limits_stand_between_their_readings():
    """Each limit of the cell's own size against the readings it was
    set from (``check.set_from``; PERF.md section 2): three times the
    largest of 29 sound seeds, which the driver's seed 134443610 set
    for the two norm gaps, and under what the planted fault of this
    model's own, a head's decay replaced by its mean, reads."""
    limits = CONFIG["check"]["limits"]
    sound = {"loss_gap": 1.62e-4, "grad_norm_gap": 0.1027,
             "update_norm_gap": 0.0612}
    mean_decay = {"loss_gap": 0.0118, "grad_norm_gap": 0.794,
                  "update_norm_gap": 0.792}
    assert set(limits) == set(sound)
    for name, limit in limits.items():
        assert 3 * sound[name] <= limit <= mean_decay[name] / 2, name
    assert limits["update_norm_gap"] < 1.0  # a step that changes nothing
    assert "134443610" in CONFIG["check"]["set_from"]


def test_every_number_of_the_catalogs_row_is_in_the_file(manifest):
    """The row's ``config`` as the catalog of public architectures has
    it: every key under the same name, the value its own unless the key
    is in ``reduced``."""
    row = {
        "image_patch_token": 157157, "video_patch_token": 156909,
        "image_start_token": 157158, "video_start_token": 157160,
        "num_hidden_layers": 42, "hidden_size": 2560,
        "intermediate_size": 6144, "first_k_dense_replace": 2,
        "max_position_embeddings": 131072, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "num_attention_heads": 32,
        "q_lora_rank": None, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts": 512,
        "num_key_value_heads": 32, "rope_theta": 6000000,
        "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
        "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
        "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
        "use_qk_norm": True, "score_function": "sigmoid",
        "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
        "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
        "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
        "short_conv_kernel_size": 4, "use_nGPT": False,
        "scale_router_input": False, "value_norm": False,
        "up_proj_norm": False,
        "gated_attention_proj_granularity_type": "head_wise",
        "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
        "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
        "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
        "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}
    assert len(row) == 51
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
    # a metric without a list would have to be reported here too
    assert all("workloads" in m or m["name"] == "setup_s"
               or m["moves"] == "setup_s"
               for m in manifest["end_to_end"] + manifest["per_layer"])


# -- counts, by hand ---------------------------------------------------------
D, H, KD, QK, VD, RANK, ROPE, MLP, W, E, HELD, K, V, S = \
    2560, 32, 128, 192, 128, 512, 64, 6144, 768, 512, 8, 8, 19648, 16384
WIDTH = H * KD
KDA_MATMUL = 4 * D * WIDTH + 2 * D * H + WIDTH * D
KDA = KDA_MATMUL + 4 * 3 * WIDTH + H + WIDTH + KD
LATENT_MATMUL = D * H * QK + D * (RANK + ROPE) + RANK * H * (KD + VD) \
    + D * H + H * VD * D
LATENT = LATENT_MATMUL + RANK + 2 * QK
EXPERT = 3 * D * W


def test_the_configuration_holds_822_036_800_parameters():
    dense_layer = KDA + 2 * D + 3 * D * MLP
    own = D * E + E + EXPERT                # router, bias, shared expert
    sparse_kda = KDA + 2 * D + own + HELD * EXPERT
    sparse_latent = LATENT + 2 * D + own + HELD * EXPERT
    assert (KDA, LATENT) == (52_646_048, 31_966_080)
    assert dense_layer == 99_837_088
    want = dense_layer + 5 * sparse_kda + sparse_latent + 2 * V * D + D
    assert want == 822_036_800 == FAMILY.param_count(SZ)
    assert 12 * want == pytest.approx(9.864e9, rel=1e-3)
    # whole, one layer's experts: 3.02B parameters, 36 GB at 12 bytes
    assert E * EXPERT == pytest.approx(3.02e9, rel=1e-3)
    # beside the experts a layer is ISSUE 41's "about 56M"
    per_layer = (5 * KDA + LATENT) / 6 + own - E
    assert per_layer == pytest.approx(56.4e6, rel=2e-3)


def test_a_token_costs_3_53_gflop():
    """6 per matmul parameter a token meets (an eighth of a routed
    expert a layer by expectation); attention by the causal half at 192
    and 128, the rule by its recurrence, the convolution's taps, three
    forwards' worth."""
    routed = K * HELD / E
    assert routed == 0.125
    matmul = 6 * KDA_MATMUL + LATENT_MATMUL + 3 * D * MLP \
        + 6 * (D * E + EXPERT + routed * EXPERT) + D * V
    assert FAMILY.matmul_params_per_token(SZ) == matmul
    assert matmul == pytest.approx(492.7e6, rel=1e-3)    # ISSUE 41: "493M"
    attention = 3 * H * 2 * (QK + VD) * (S + 1) / 2
    rule = 3 * 6 * 7 * H * KD * KD
    conv = 3 * 6 * 2 * 4 * 3 * WIDTH
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(
        6 * matmul + attention + rule + conv, rel=1e-12)
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(3.527e9, rel=1e-3)
    assert FAMILY.samples_per_row(SZ) == S


def test_the_rule_needs_1_44_tflop_and_13_7_gb_a_step():
    """The recurrence's seven operations a state entry, position and
    head, four forwards' worth, six layers; q, k, v, o and their
    gradients in bfloat16, the decay a float32 vector as wide as k."""
    ops = 6 * 4 * 7 * S * H * KD * KD
    assert kda_flops.rule_ops_per_step(1, S, H, KD, KD, 6) == ops \
        == pytest.approx(1.443e12, rel=1e-3)
    qk, decay, beta = S * H * KD * 2, S * H * KD * 4, S * H * 4
    bytes_ = 6 * ((2 * qk + 2 * qk + decay + beta)
                  + (4 * qk + 3 * qk + 2 * decay + 2 * beta))
    assert kda_flops.rule_bytes_per_step(1, S, H, KD, KD, 6) == bytes_ \
        == pytest.approx(13.728e9, rel=1e-3)
    assert bytes_ / 819e9 > ops / 197e12            # memory-bound
    assert kda_flops.rule_forward_ops(1, 1, H, KD, KD) == 7 * H * KD * KD


def test_the_flash_kernels_need_12_6_tflop_a_step_at_192_over_128():
    """The causal half's scores a head; q k^T and ds k at the score
    head's 192, p v and do v^T at the value head's 128."""
    scores = S * (S + 1) // 2
    per_score = 2 * ((QK + VD) + (2 * QK + VD) + (2 * QK + 2 * VD))
    assert per_score == 2 * (5 * QK + 4 * VD)
    flops = H * per_score * scores
    assert hybrid_flops.flash_flops_per_step(1, H, S, QK, VD, [None]) \
        == flops == pytest.approx(12.645e12, rel=1e-3)
    # one head size of 256 (what mla_flash_roofline would count) is not it
    assert flops != 18 * 256 * H * scores
    bytes_ = hybrid_flops.flash_bytes_per_step(1, H, H, S, QK, VD, 1)
    assert flops / 197e12 > bytes_ / 819e9          # compute-bound


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
    assert (entry["unit"] == "%") == (name in KERNEL_READERS)


def test_the_scope_reader_sums_the_kda_scopes_less_the_kernels(monkeypatch):
    """A table of one instruction a scope and an event of its own
    length for each, the two kernels under ``kda.rule``: the reader's
    sum is the ``kda.*`` scopes' lengths less the kernels', a step."""
    from chipbench import scope_readers
    from horovod_tpu import spmd
    from horovod_tpu.common import trace as program_trace
    scopes = ["kda.proj", "kda.conv", "kda.gate", "kda.rule", "kda.norm",
              "mla", "moe", "moe.route", "moe.shared", "mlp", "lm_head_loss",
              "loss", "optimizer", "embed"]
    assert set(scopes) <= set(program_trace.DEVICE_SCOPES)
    table = {f"fusion.{i}": scope for i, scope in enumerate(scopes)}
    table.update({"kda_fwd.1": "kda.rule", "kda_bwd.2": "kda.rule"})
    monkeypatch.setattr(scope_readers, "noted_table", lambda: table)
    monkeypatch.setattr(spmd, "scope_of",
                        lambda t, name: t.get(name.split("[", 1)[0]))
    ms, at, events = 1e6, 0.0, []
    for i in range(len(scopes)):
        events.append((f"fusion.{i}", at, (i + 1) * ms))
        at += (i + 1) * ms
    for name in ("kda_fwd.1[tpu_custom_call]", "kda_bwd.2[tpu_custom_call]"):
        events.append((name, at, 100 * ms))
        at += 100 * ms
    ctx = ctx_of(object(), {"events": {"/device:TPU:0": events},
                            "busy_s": at / 1e9, "window_s": at / 1e9})
    ctx["steps"] = 2
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)
    assert read("kda_outside_kernels_ms_per_step") == pytest.approx(
        (1 + 2 + 3 + 4 + 5) / 2)
    assert read("attn_outside_kernels_ms_per_step") == pytest.approx(6 / 2)
    assert read("mlp_ms_per_step") == pytest.approx((9 + 10) / 2)
    assert any(n.startswith("scopes:") for n in ctx["notes"])
    # a program without a table (the parent commit): nothing to read
    monkeypatch.setattr(scope_readers, "noted_table", lambda: None)
    ctx.pop("device_scopes")
    assert read("kda_outside_kernels_ms_per_step") is None


def test_the_kernel_readers_count_a_hand_made_trace_at_this_shape():
    """A trace of one device with the rule's two kernels, the three
    flash kernels, the grouped products and another custom call: each
    reader takes its own by name; a share stays under 100 where the
    kernels take longer than their least."""
    from chipbench import peaks
    ms = 1e6
    events = [("kda_fwd.7[tpu_custom_call]", 0.0, 100 * ms),
              ("kda_bwd.8[tpu_custom_call]", 100 * ms, 150 * ms),
              ("flash_fwd.3[tpu_custom_call]", 250 * ms, 35 * ms),
              ("flash_bwd_dq.4[tpu_custom_call]", 285 * ms, 45 * ms),
              ("flash_bwd_dkv.5[tpu_custom_call]", 330 * ms, 45 * ms),
              ("ragged-dot.11", 375 * ms, 25 * ms),
              ("other.2[tpu_custom_call]", 400 * ms, 100 * ms),
              ("fusion.9", 500 * ms, 500 * ms)]
    trace = {"events": {"/device:TPU:0": events}, "busy_s": 1.0,
             "window_s": 1.0}
    ctx = ctx_of(peaks.chip_peak("TPU v5 lite"), trace)
    ctx["steps"] = 1
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)
    assert read("kda_time_share") == pytest.approx(25.0)
    assert read("latent_flash_time_share") == pytest.approx(12.5)
    rule_least = kda_flops.rule_bytes_per_step(1, S, H, KD, KD, 6) / 819e9
    assert rule_least == pytest.approx(16.76e-3, rel=1e-3)
    assert read("kda_roofline") == pytest.approx(
        100 * rule_least / 0.25, rel=1e-6)
    flash_least = 2 * (5 * QK + 4 * VD) * H * (S * (S + 1) // 2) / 197e12
    assert flash_least == pytest.approx(64.19e-3, rel=1e-3)
    assert read("latent_flash_roofline") == pytest.approx(
        100 * flash_least / 0.125, rel=1e-6)
    assert 0 < read("kda_roofline") < 100
    assert 0 < read("latent_flash_roofline") < 100
    assert any("kda_roofline: memory-bound" in n for n in ctx["notes"])
    assert any("latent_flash_roofline: compute-bound" in n
               for n in ctx["notes"])
    # two steps in the same trace: half the time a step
    ctx["steps"] = 2
    assert read("kda_roofline") == pytest.approx(
        100 * 2 * rule_least / 0.25, rel=1e-6)
    # a trace without the kernels gives nothing to read
    ctx["trace"] = dict(trace, events={"/device:TPU:0": events[-3:]})
    for name in KERNEL_READERS:
        assert read(name) is None, name


def test_the_latent_readers_leave_a_family_of_one_head_size_alone():
    """Laid over a cell whose sizes name no ``nope`` and ``v_dim`` (the
    grouped-head families), the readers report nothing and do not
    raise."""
    ctx = ctx_of(object(), {"events": {}, "busy_s": 1.0})
    ctx["sz"] = {"heads": 32, "head_dim": 64, "seq": 8192,
                 "per_chip_batch": 4}
    for name in ("latent_flash_time_share", "latent_flash_roofline"):
        assert harness.load_module("layer_metrics", name).read(ctx) is None
