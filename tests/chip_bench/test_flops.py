"""The yardstick's arithmetic against hand-worked values."""

import pytest

from . import _paths  # noqa: F401
from chipbench import flops, peaks

PYTHIA = dict(vocab=50304, layers=12, d=2048, mlp=8192)


def test_pythia_l12_has_810_1_million_parameters():
    # 12 x (4 x 2048^2 + 2 x 2048 x 8192 + 2 x 2048) + 2 x 50304 x 2048 + 2048
    assert flops.lm_param_count(**PYTHIA) == 810_076_160
    assert flops.lm_matmul_params(**PYTHIA) == 810_076_160 - 50304 * 2048


def test_cell_one_needs_3_970e13_flops_a_step():
    per_token = flops.lm_flops_per_token(seq=2048, **PYTHIA)
    assert per_token == 6 * 707_053_568 + 12 * 12 * 2048 * 2048
    step = flops.lm_flops_per_step(seq=2048, batch=4, **PYTHIA)
    assert step == pytest.approx(3.970e13, rel=5e-4)


def test_mfu_of_pr_22s_rate_is_what_the_ledger_printed():
    # 27,402 tokens/s x 4.8463 GFLOPs a token / 197 TFLOP/s = 67.41 %
    mfu = 27402 * flops.lm_flops_per_token(seq=2048, **PYTHIA) / 197e12
    assert 100 * mfu == pytest.approx(67.41, abs=0.01)


def test_resnet50_training_image_is_three_forward_passes_of_two_flops_a_mac():
    assert flops.resnet50_flops_per_image() == 3 * 2 * 4.089e9


def test_flash_flops_count_the_causal_half_once():
    # one head, one layer, one row: nine products of S^2 D each
    assert flops.flash_flops_per_step(1, 1, 2048, 128, 1) \
        == 9 * 2048 * 2048 * 128
    full = flops.flash_flops_per_step(4, 16, 2048, 128, 12)
    assert full == 9 * 2048 * 2048 * 128 * 4 * 16 * 12 == 3710851743744


def test_flash_bytes_read_and_write_every_operand_once():
    tensor, stat = 2048 * 128 * 2, 2048 * 4
    assert flops.flash_bytes_per_step(1, 1, 2048, 128, 1) \
        == 15 * tensor + 5 * stat


def test_roofline_names_the_bound():
    t, bound = flops.roofline_seconds(197e12, 1e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.roofline_seconds(1e9, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")


def test_peak_table_knows_v5e_and_refuses_unknown_chips():
    peak = peaks.chip_peak("TPU v5 lite")
    assert (peak.bf16_flops, peak.hbm_bytes) == (197e12, 819e9)
    assert "v5e" in peak.source
    for kind in ("TPU v9 mega", "cpu", ""):
        with pytest.raises(LookupError, match="peak table"):
            peaks.chip_peak(kind)


@pytest.mark.parametrize("kind", sorted(peaks.PEAKS))
def test_every_peak_names_its_source(kind):
    peak = peaks.PEAKS[kind]
    assert peak.bf16_flops > 0 and peak.hbm_bytes > 0
    assert "Google Cloud TPU documentation" in peak.source


def test_mfu_has_nothing_to_read_at_a_rehearsal_and_raises_on_the_chip():
    """A rehearsal has no peak and sizes no FLOP count is kept for:
    ``None``, for every family. With a chip's peak a ResNet that is not
    ResNet-50 at 224 stops the run, and ResNet-50 reads its share."""
    from chipbench import harness, readers
    resnet = harness.load_module("families", "resnet")
    lm = harness.load_module("families", "transformer_lm")
    tiny = {"stages": [1, 1], "filters": 8, "image": 32}
    full = {"stages": [3, 4, 6, 3], "filters": 64, "image": 224}
    ctx = {"peak": None, "rate": 2596.9, "family": resnet, "sz": tiny}
    assert readers.mfu(ctx) is None
    assert readers.mfu(dict(ctx, sz=full)) is None
    assert readers.mfu({"peak": None, "rate": 1.0, "family": lm,
                        "sz": {}}) is None
    ctx["peak"] = peaks.chip_peak("TPU v5 lite")
    with pytest.raises(ValueError, match="ResNet-50's at 224x224"):
        readers.mfu(ctx)
    assert readers.mfu(dict(ctx, sz=full)) == pytest.approx(32.34, abs=0.01)
