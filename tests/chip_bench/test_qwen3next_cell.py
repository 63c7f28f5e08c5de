"""The cell ``qwen3next-injit-1chip`` (family ``qwen3next_lm``): its
check passes at the rehearsal's size and fails with bfloat16
parameters, its file holds the published widths and the cut, its counts
are the hand-computed ones (the rule's recurrence, at a second shape
too, and the readers it joins among them), it is in every list it
joined (PR 37's scope metrics and the two ``gdn_*`` ones among them
since PR 45) whoever else is, and its readers have nothing to report at
a rehearsal. What is asserted of the manifest is asserted of the
root's and of the one the next PR would leave (``conftest.py``)."""

import inspect
import json
import os

import pytest

from . import _paths
from chipbench import check, gdn_flops, harness, moe_flops

M = _paths.manifest()
CELL = "qwen3next-injit-1chip"
FAMILY = harness.load_module("families", "qwen3next_lm")
with open(os.path.join(_paths.BENCH, "configs",
                       "qwen3-next-80b-a3b-ep16-l4.json")) as f:
    CONFIG = json.load(f)
SZ = FAMILY.sizes(CONFIG, CONFIG["assumed"]["per_chip_batch"])
NEW_READERS = ("gdn_time_share", "gdn_roofline")
SCOPE_READERS = ("gdn_outside_kernels_ms_per_step", "gdn_conv_ms_per_step")
JOINED = ("tokens_per_s_chip", "step_p90_ms", "mfu.lm",
          "device_idle_share.lm", "hbm_need_gb.lm", "moe_grouped_time_share",
          "moe_grouped_roofline", "moe_load_max_over_mean",
          "moe_dropped_share", "mla_flash_time_share", "mla_flash_roofline",
          "head_loss_ms_per_step", "unscoped_ms_per_step",
          "attn_outside_kernels_ms_per_step", "mlp_ms_per_step",
          "moe_route_ms_per_step", "moe_dispatch_combine_ms_per_step")
CHUNK_GAUGE = 'hvd_gdn_chunks{kind="chunk_length"}'

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


def test_the_file_states_the_published_widths_and_the_cut():
    assert CONFIG["source"].endswith(
        "Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert (SZ["d"], SZ["key_heads"], SZ["value_heads"], SZ["key_dim"],
            SZ["value_dim"], SZ["conv"]) == (2048, 16, 32, 128, 128, 4)
    assert (SZ["heads"], SZ["kv_heads"], SZ["head_dim"], SZ["rotary"]) \
        == (16, 2, 256, 64)
    assert (SZ["expert_mlp"], SZ["shared_mlp"], SZ["experts"], SZ["top_k"]) \
        == (512, 512, 512, 10)
    assert sorted(CONFIG["reduced"]) == ["num_experts", "num_hidden_layers",
                                         "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert SZ["kept"] == (0, 1, 2, 3)
    assert FAMILY.kinds(SZ) == ["delta", "delta", "delta", "attention"]
    assert (FAMILY.delta_layers(SZ), FAMILY.attention_layers(SZ),
            FAMILY.expert_layers(SZ)) == (3, 1, 4)
    dep = CONFIG["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["router_width"],
            dep["expert_offset"]) == (16, 512, 0)
    assert SZ["experts_held"] * 16 == 512 and SZ["vocab"] * 8 == 151936
    assert SZ["seq"] == 16384 and SZ["per_chip_batch"] == 1
    for key in ("gates", "gates_why", "qkvz_order", "rotary_pairing",
                "norms", "sequence_length", "per_chip_batch", "optimizer",
                "activations"):
        assert key in CONFIG["assumed"], key
    assert any("multi-token" in d for d in CONFIG["departures"])


def test_every_number_of_the_catalogs_row_is_in_the_file(manifest):
    """The row's ``config`` as the catalog of public architectures has
    it: every key under the same name, the value its own unless the key
    is in ``reduced``."""
    row = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    for key, value in row.items():
        assert key in CONFIG, key
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value
            assert CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    entry = {c["name"]: c for c in manifest["configs"]}[
        "qwen3-next-80b-a3b-ep16-l4"]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]


def test_the_cell_is_in_every_list_it_joins(manifest):
    """In each of ``JOINED``, ``NEW_READERS`` and ``SCOPE_READERS``,
    once; what else lists the cell, and which cells stand beside or
    behind it, is the manifest's (``test_manifest.py`` holds every list
    to the rules, and ``test_rehearse.py`` every listed reader to
    reading its cell)."""
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("qwen3-next-80b-a3b-ep16-l4", "injit-1chip", 1)
    listed = _paths.listed_for(manifest, CELL)
    assert set(JOINED) | set(NEW_READERS) | set(SCOPE_READERS) \
        <= set(listed)
    assert len(listed) == len(set(listed))


# -- counts, by hand ---------------------------------------------------------
D, HK, HV, DH, TAPS, H, KV, HD, W, E, HELD, K, V, S = \
    2048, 16, 32, 128, 4, 16, 2, 256, 512, 512, 32, 10, 18992, 16384
KEYS, VALUES = HK * DH, HV * DH
DELTA = (D * (2 * KEYS + 2 * VALUES) + D * 2 * HV + TAPS * (2 * KEYS + VALUES)
         + HV + HV + DH + VALUES * D)
ATTN = D * H * 2 * HD + 2 * D * KV * HD + H * HD * D + 2 * HD
EXPERT = 3 * D * W
BESIDE = D * E + 3 * D * W + D              # router, shared expert, its gate


def test_the_configuration_holds_625_7_million_parameters():
    want = 3 * DELTA + ATTN + 4 * (BESIDE + HELD * EXPERT + 2 * D) \
        + 2 * V * D + D
    assert FAMILY.param_count(SZ) == want
    assert want == pytest.approx(625.7e6, rel=1e-4)
    assert 12 * want == pytest.approx(7.51e9, rel=1e-3)
    # the layers as ISSUE 33 counts them, in millions
    assert DELTA == pytest.approx(33.72e6, rel=1e-3)
    assert ATTN == pytest.approx(27.26e6, rel=1e-3)
    assert BESIDE == pytest.approx(4.20e6, rel=1e-3)
    assert EXPERT == pytest.approx(3.146e6, rel=1e-3)
    # whole, by the same equations: the published 80B
    whole = 36 * DELTA + 12 * ATTN + 48 * (BESIDE + 512 * EXPERT + 2 * D) \
        + 2 * 151936 * D + D
    assert whole == pytest.approx(79.67e9, rel=1e-3)


# batch, positions, key heads, value heads, key head, value head, layers;
# one forward's operations, what the kernels are handed a layer (bytes)
RULE_SHAPES = {
    # the cell's own: 16 key heads under 32 value heads, both of 128
    "cell": ((1, S, HK, HV, DH, DH, 3), 60.1e9, 1_086_324_736),
    # 30 heads whose key is 96 wide and whose value 192 (neither a
    # multiple of the 128 lanes, nor of a width with the other), two
    # rows of 8,192: entries 2 x 8192 x 30 x 96 x 192 = 9,059,696,640;
    # q, k 94,371,840 bytes each, v 188,743,680, a gate 1,966,080
    "96_by_192": ((2, 8192, 30, 30, 96, 192, 3), 63.4e9, 1_521_745_920),
}


@pytest.mark.parametrize("shape", sorted(RULE_SHAPES))
def test_the_rule_is_counted_by_its_recurrence(shape):
    """Seven operations a state entry and position: the decay, ``S^T
    k``, the rank-one update, ``S^T q``; a step is four forwards'
    worth; the bytes are the kernels' operands. From the shapes alone,
    so at a second shape as at the cell's."""
    (b, s, hk, hv, dk, dv, layers), forward, handed = RULE_SHAPES[shape]
    entry_steps = b * s * hv * dk * dv
    assert gdn_flops.rule_forward_ops(b, s, hv, dk, dv) == 7 * entry_steps \
        == pytest.approx(forward, rel=1e-3)
    assert gdn_flops.rule_ops_per_step(b, s, hv, dk, dv, layers) \
        == layers * 4 * 7 * entry_steps
    qk, v, gate = b * s * hk * dk * 2, b * s * hv * dv * 2, b * s * hv * 4
    assert (2 * qk + 2 * v + 2 * gate) + (4 * qk + 3 * v + 4 * gate) \
        == handed
    assert gdn_flops.rule_bytes_per_step(b, s, hk, hv, dk, dv, layers) \
        == layers * handed
    if shape == "cell":
        assert layers * 4 * 7 * entry_steps \
            == pytest.approx(721.6e9, rel=1e-3)
    else:
        assert 7 * entry_steps == 63_417_876_480
        assert layers * 4 * 7 * entry_steps == 761_014_517_760
        assert layers * handed == 4_565_237_760
        # memory-bound here too: the bytes take longer than the products
        assert layers * handed / 819e9 \
            > layers * 4 * 7 * entry_steps / 197e12
    # nothing the kernels choose for themselves is in the count: the
    # reader has no chunk length to give it, and the states a kernel
    # keeps between its two passes are left out
    assert "chunk" not in inspect.signature(
        gdn_flops.rule_bytes_per_step).parameters
    assert "registry" not in inspect.getsource(
        harness.load_module("layer_metrics", "gdn_roofline"))


def test_a_token_costs_1_59_gflop():
    """6 per matmul parameter a token meets (0.625 routed experts by
    expectation); attention by the causal half, the rule by its
    recurrence and the convolution's taps, three forwards' worth."""
    mixers = 3 * (D * (2 * KEYS + 2 * VALUES) + D * 2 * HV + VALUES * D) \
        + D * H * 2 * HD + 2 * D * KV * HD + H * HD * D
    routed = K * HELD / E
    assert routed == 0.625
    matmul = mixers + 4 * (BESIDE + routed * EXPERT) + D * V
    assert FAMILY.matmul_params_per_token(SZ) == matmul
    attention = 3 * H * 2 * 2 * HD * (S + 1) / 2
    rule = 3 * 3 * 7 * HV * DH * DH
    conv = 3 * 3 * 2 * TAPS * (2 * KEYS + VALUES)
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(
        6 * matmul + attention + rule + conv, rel=1e-12)
    assert FAMILY.flops_per_sample(SZ) == pytest.approx(1.587e9, rel=1e-3)
    forward = FAMILY.flops_per_sample(SZ) / 3
    assert forward == pytest.approx(529e6, rel=2e-3)
    # the shares ISSUE 33 sizes the cell by
    assert 2 * D * V / forward == pytest.approx(0.147, abs=0.005)
    assert (H * 2 * 2 * HD * (S + 1) / 2 + 2 * ATTN) / forward \
        == pytest.approx(0.36, abs=0.02)
    assert 3 * (2 * DELTA + 7 * HV * DH * DH) / forward \
        == pytest.approx(0.40, abs=0.02)


# -- the readers ---------------------------------------------------------------

def ctx_of(peak, trace, registry):
    return {"peak": peak, "trace": trace, "registry": registry, "sz": SZ,
            "family": FAMILY, "steps": 7, "notes": []}


@pytest.mark.parametrize("name", NEW_READERS + SCOPE_READERS)
def test_a_new_reader_has_nothing_to_report_at_a_rehearsal(name, manifest):
    reader = harness.load_module("layer_metrics", name)
    assert reader.read(ctx_of(None, None, {CHUNK_GAUGE: 64})) is None
    assert reader.read(ctx_of(None, None, {})) is None
    entry = {x["name"]: x for x in manifest["per_layer"]}[name]
    assert (reader.LAYER, reader.UNIT, reader.MOVES) \
        == (entry["layer"], entry["unit"], entry["moves"])
    assert CELL in entry["workloads"]


def test_the_readers_match_kernels_by_name_and_count_this_shape():
    """A trace of one device with the flash kernels, the rule's two,
    the grouped products and another custom call: each reader takes
    its own, the readers the cell joins count from ``sz`` what a hand
    counts at this shape, and a share stays under 100 where the
    kernels take longer than their least."""
    from chipbench import peaks
    ms = 1e6
    events = [("flash_fwd.3[tpu_custom_call]", 0.0, 30 * ms),
              ("flash_bwd_dq.4[tpu_custom_call]", 30 * ms, 40 * ms),
              ("flash_bwd_dkv.5[tpu_custom_call]", 70 * ms, 30 * ms),
              ("gdn_fwd.7[tpu_custom_call]", 100 * ms, 40 * ms),
              ("gdn_bwd.8[tpu_custom_call]", 140 * ms, 110 * ms),
              ("ragged-dot.11", 250 * ms, 20 * ms),
              ("other.2[tpu_custom_call]", 270 * ms, 30 * ms),
              ("fusion.9", 300 * ms, 200 * ms)]
    trace = {"events": {"/device:TPU:0": events}, "busy_s": 0.5,
             "window_s": 0.5}
    rows = 4 * 10240.0
    registry = {CHUNK_GAUGE: 64, "hvd_moe_steps_total": 1,
                'hvd_moe_assignments_total{held="1"}': rows}
    peak = peaks.chip_peak("TPU v5 lite")
    ctx = ctx_of(peak, trace, registry)
    ctx["steps"] = 1
    read = lambda name: harness.load_module("layer_metrics", name).read(ctx)
    assert read("gdn_time_share") == pytest.approx(30.0)
    assert read("mla_flash_time_share") == pytest.approx(20.0)
    assert read("moe_grouped_time_share") == pytest.approx(4.0)
    # the rule: memory-bound by what the kernels are handed, whatever
    # chunk the program says it took
    ops = 3 * 4 * 7 * S * HV * DH * DH
    bytes_ = gdn_flops.rule_bytes_per_step(1, S, HK, HV, DH, DH, 3)
    assert bytes_ / 819e9 > ops / 197e12
    assert read("gdn_roofline") == pytest.approx(
        100 * (bytes_ / 819e9) / 0.15, rel=1e-6)
    assert 0 < read("gdn_roofline") < 100
    assert any("gdn_roofline: memory-bound" in n for n in ctx["notes"])
    with_64 = read("gdn_roofline")
    ctx["registry"] = dict(registry, **{CHUNK_GAUGE: 128})
    assert read("gdn_roofline") == with_64
    ctx["registry"] = registry
    # the flash kernels: nine products of the causal half, 16 query
    # heads of 256, one layer
    least = 9 * S * S * HD * H / 197e12
    assert read("mla_flash_roofline") == pytest.approx(
        100 * least / 0.1, rel=1e-6)
    assert 0 < read("mla_flash_roofline") < 100
    # the grouped products: twelve a layer over the rows the program
    # counted, the held kernels once a product and layer
    flops = 2 * rows * D * W * 12
    traffic = 2 * 12 * (rows * (D + W) + 4 * HELD * D * W)
    assert moe_flops.expected_rows_per_layer(S, K, HELD, E) == 10240
    least = max(flops / 197e12, traffic / 819e9)
    assert read("moe_grouped_roofline") == pytest.approx(
        100 * least / 0.02, rel=1e-6)
    assert 0 < read("moe_grouped_roofline") < 100
    # a trace without the kernels (the parent's) gives nothing to read
    ctx["trace"] = {"events": {"/device:TPU:0": events[-2:]}, "busy_s": 0.5,
                    "window_s": 0.5}
    assert read("gdn_roofline") is None and read("gdn_time_share") is None
