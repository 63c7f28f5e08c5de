"""The cell ``lfm2moe-injit-1chip`` (family ``lfm2_moe_lm``): its check
passes at the rehearsal's size, fails with bfloat16 parameters and comes
out not correct with its step broken; its file holds the published
widths and the cut; its counts are the hand-computed ones; it is in
every list it joined (PR 37's scope metrics among them since PR 45) and
in its five new ones, whoever else is; its new readers have nothing to
report at a rehearsal and count a hand-made trace at this shape, k and
v by key-value head. What is asserted of the manifest is asserted of
the root's and of the one the next PR would leave (``conftest.py``).
(Cold on this sandbox: 45 s.)"""

import argparse
import json
import os
import time

import pytest

from . import _paths
from chipbench import check, harness, hybrid_flops

M = _paths.manifest()
CELL = "lfm2moe-injit-1chip"
NAME = "lfm2-24b-a2b-ep8-l9"
FAMILY = harness.load_module("families", "lfm2_moe_lm")
with open(os.path.join(_paths.BENCH, "configs", f"{NAME}.json")) as f:
    CONFIG = json.load(f)
SZ = FAMILY.sizes(CONFIG, CONFIG["assumed"]["per_chip_batch"])
SCOPE_READERS = {
    "shortconv_ms_per_step": ("shortconv.proj", "shortconv.conv"),
    "shortconv_gate_conv_ms_per_step": ("shortconv.conv",),
    "moe_layer_ms_per_step": ("moe", "moe.route", "moe.dispatch",
                              "moe.experts", "moe.combine")}
FLASH_READERS = ("gqa_flash_time_share", "gqa_flash_roofline")
NEW_READERS = tuple(SCOPE_READERS) + FLASH_READERS
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
        > 5 * limits["update_norm_gap"]


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
        "LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert (SZ["d"], SZ["heads"], SZ["kv_heads"], SZ["head_dim"],
            SZ["taps"], SZ["mlp"]) == (2048, 32, 8, 64, 3, 11776)
    assert (SZ["expert_mlp"], SZ["experts"], SZ["top_k"], SZ["scale"],
            SZ["topk_eps"], SZ["eps"], SZ["theta"]) \
        == (1536, 64, 4, 1.0, 1e-6, 1e-5, 1e6)
    assert sorted(CONFIG["reduced"]) == ["num_experts", "num_hidden_layers",
                                         "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 40,
                                   "num_experts": 64, "vocab_size": 65536}
    assert SZ["kept"] == (0, 2, 3, 4, 5, 6, 7, 8, 9)
    assert [i for i in SZ["kept"]
            if CONFIG["layer_types"][i] == "full_attention"] == [2, 6]
    assert (FAMILY.conv_layers(SZ), FAMILY.attention_layers(SZ),
            FAMILY.expert_layers(SZ)) == (7, 2, 8)
    dep = CONFIG["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["router_width"],
            dep["expert_offset"]) == (8, 64, 0)
    assert SZ["experts_held"] * 8 == 64 and SZ["vocab"] * 8 == 65536
    assert SZ["seq"] == 8192 and SZ["per_chip_batch"] == 4
    assert "PLACEHOLDER" not in json.dumps(CONFIG)
    for key in ("expert_load", "what"):
        assert dep[key], key
    for key in ("head_dim", "head_dim_why", "tie_word_embeddings",
                "topk_weight_eps", "topk_weight_eps_why", "rotary_pairing",
                "norms", "conv_order", "sequence_length", "per_chip_batch",
                "optimizer", "activations"):
        assert key in CONFIG["assumed"], key
    assert any("expert bias" in d for d in CONFIG["departures"])
    assert CONFIG["check"]["set_from"] and CONFIG["rehearse"]["check"]


def test_every_number_of_the_catalogs_row_is_in_the_file(manifest):
    """The row's ``config`` as the catalog of public architectures has
    it: every key under the same name, the value its own unless the key
    is in ``reduced``."""
    row = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776,
        "layer_types": ["full_attention" if i % 4 == 2 else "conv"
                        for i in range(40)],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
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
D, H, KV, HD, MLP, W, E, HELD, K, V, S, B = \
    2048, 32, 8, 64, 11776, 1536, 64, 8, 4, 8192, 8192, 4
CONV = 4 * D * D + 3 * D                    # two projections and three taps
ATTN = D * H * HD + 2 * D * KV * HD + H * HD * D + 2 * HD
DENSE = 3 * D * MLP
EXPERT = 3 * D * W
ROUTER = D * E + E


def test_the_configuration_holds_832_652_032_parameters():
    layer_0 = CONV + 2 * D + DENSE
    sparse_conv = CONV + 2 * D + ROUTER + HELD * EXPERT
    sparse_attn = ATTN + 2 * D + ROUTER + HELD * EXPERT
    assert (layer_0, sparse_conv, sparse_attn) \
        == (89_139_200, 92_416_064, 86_118_592)
    want = layer_0 + 6 * sparse_conv + 2 * sparse_attn + V * D + D
    assert want == 832_652_032 == FAMILY.param_count(SZ)
    assert 12 * want == pytest.approx(9.99e9, rel=1e-3)
    # whole, by the same equations: the 24B-A2B of its name
    whole_moe = ROUTER + 64 * EXPERT
    whole = 2 * (CONV + 2 * D + DENSE) + 28 * (CONV + 2 * D + whole_moe) \
        + 10 * (ATTN + 2 * D + whole_moe) + 65536 * D + D
    assert whole == pytest.approx(23.84e9, rel=1e-3)
    assert whole + 65536 * D == pytest.approx(23.98e9, rel=1e-3)  # untied
    active = whole - 38 * 60 * EXPERT
    assert active == pytest.approx(2.327e9, rel=1e-3)    # ISSUE 39: "2.32B"
    assert 64 * EXPERT == pytest.approx(604.0e6, rel=1e-3)


def test_a_token_costs_1_80_gflop():
    """6 per matmul parameter a token meets (half a routed expert a
    layer by expectation); attention by the causal half, the
    convolution's taps and two gates, three forwards' worth."""
    routed = K * HELD / E
    assert routed == 0.5
    matmul = 7 * 4 * D * D + 2 * (ATTN - 2 * HD) + DENSE \
        + 8 * (D * E + routed * EXPERT) + D * V
    assert FAMILY.matmul_params_per_token(SZ) == matmul
    attention = 3 * 2 * H * 2 * 2 * HD * (S + 1) / 2
    conv = 3 * 7 * (2 * 3 + 2) * D
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(
        6 * matmul + attention + conv, rel=1e-12)
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(1.7997e9, rel=1e-4)
    assert FAMILY.samples_per_row(SZ) == S
    # a step: 59 TFLOP, 0.30 s at the chip's peak
    assert B * S * FAMILY.flops_per_sample(SZ) == pytest.approx(
        58.97e12, rel=1e-3)


def test_the_flash_kernels_need_9_9_tflop_a_step_k_and_v_by_their_own_heads():
    """Nine products of the causal half a query head; q, o, do, dq a
    query head and k, v, dk, dv a key-value head, each once a kernel
    that touches it."""
    scores = S * (S + 1) // 2
    flops = B * H * 18 * HD * scores * 2
    assert hybrid_flops.flash_flops_per_step(B, H, S, HD, HD, [None, None]) \
        == flops == pytest.approx(9.897e12, rel=1e-3)
    q_side = (HD + HD) + (3 * HD) + (HD + HD)
    kv_side = 2 * HD + 2 * HD + 4 * HD
    bytes_ = B * 2 * (H * S * (2 * q_side + 20) + KV * S * 2 * kv_side)
    assert hybrid_flops.flash_bytes_per_step(B, H, KV, S, HD, HD, 2) \
        == bytes_ == 2_457_862_144
    # by a query head k and v would count four times as often
    assert hybrid_flops.flash_bytes_per_step(B, H, H, S, HD, HD, 2) \
        - bytes_ == B * 2 * (H - KV) * S * 2 * kv_side
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
    assert (entry["unit"] == "%") == (name in FLASH_READERS)


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_a_scope_reader_sums_its_scopes_and_no_other(name, monkeypatch):
    """A table of one instruction a scope and an event of its own
    length for each: the reader's sum is its scopes' lengths, a step."""
    from chipbench import scope_readers
    from horovod_tpu import spmd
    from horovod_tpu.common import trace as program_trace
    scopes = ["shortconv.proj", "shortconv.conv", "normed_attn", "moe",
              "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
              "mlp", "lm_head_loss", "loss", "optimizer", "embed"]
    assert set(scopes) <= set(program_trace.DEVICE_SCOPES)
    table = {f"fusion.{i}": scope for i, scope in enumerate(scopes)}
    monkeypatch.setattr(scope_readers, "noted_table", lambda: table)
    monkeypatch.setattr(spmd, "scope_of",
                        lambda t, name: t.get(name.split("[", 1)[0]))
    ms, at, events = 1e6, 0.0, []
    for i in range(len(scopes)):
        events.append((f"fusion.{i}", at, (i + 1) * ms))
        at += (i + 1) * ms
    ctx = ctx_of(object(), {"events": {"/device:TPU:0": events},
                            "busy_s": at / 1e9, "window_s": at / 1e9})
    ctx["steps"] = 2
    want = sum(i + 1 for i, s in enumerate(scopes)
               if s in SCOPE_READERS[name]) / 2
    assert harness.load_module("layer_metrics", name).read(ctx) \
        == pytest.approx(want)
    assert any(n.startswith("scopes:") for n in ctx["notes"])
    # a program without a table (the parent commit): nothing to read
    monkeypatch.setattr(scope_readers, "noted_table", lambda: None)
    ctx.pop("device_scopes")
    assert harness.load_module("layer_metrics", name).read(ctx) is None


def test_the_flash_readers_count_a_hand_made_trace_at_this_shape():
    """A trace of one device with the three flash kernels, the grouped
    products and another custom call: the flash readers take their own
    by name and count 32 query over 8 key-value heads of 64, two
    layers, four rows of 8,192; a share stays under 100 where the
    kernels take longer than their least."""
    from chipbench import peaks
    ms = 1e6
    events = [("flash_fwd.3[tpu_custom_call]", 0.0, 30 * ms),
              ("flash_bwd_dq.4[tpu_custom_call]", 30 * ms, 40 * ms),
              ("flash_bwd_dkv.5[tpu_custom_call]", 70 * ms, 30 * ms),
              ("ragged-dot.11", 100 * ms, 50 * ms),
              ("other.2[tpu_custom_call]", 150 * ms, 50 * ms),
              ("fusion.9", 200 * ms, 300 * ms)]
    trace = {"events": {"/device:TPU:0": events}, "busy_s": 0.5,
             "window_s": 0.5}
    ctx = ctx_of(peaks.chip_peak("TPU v5 lite"), trace)
    ctx["steps"] = 1
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)
    assert read("gqa_flash_time_share") == pytest.approx(20.0)
    least = 18 * HD * B * H * S * (S + 1) // 2 * 2 / 197e12
    assert least == pytest.approx(50.24e-3, rel=1e-3)
    assert read("gqa_flash_roofline") == pytest.approx(
        100 * least / 0.1, rel=1e-6)
    assert 0 < read("gqa_flash_roofline") < 100
    assert any("gqa_flash_roofline: compute-bound" in n
               for n in ctx["notes"])
    # two steps in the same trace: half the time a step
    ctx["steps"] = 2
    assert read("gqa_flash_roofline") == pytest.approx(
        100 * 2 * least / 0.1, rel=1e-6)
    # a trace without the kernels gives nothing to read
    ctx["trace"] = dict(trace, events={"/device:TPU:0": events[-3:]})
    assert read("gqa_flash_roofline") is None
    assert read("gqa_flash_time_share") is None
