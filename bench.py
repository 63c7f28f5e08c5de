"""Synthetic training benchmarks (driver-run, real TPU).

TPU-native re-founding of the reference's synthetic benchmarks
(reference: examples/pytorch_synthetic_benchmark.py:95-110,
examples/tensorflow_synthetic_benchmark.py; docs/benchmarks.md:12-33),
with THIS framework in the measured loop the way a user would run it:
``horovod_tpu.jax.DistributedOptimizer`` wrapping the optax
transformation inside a shard_map'd train step over the device mesh
(gradient pmean over the data axis), parameters broadcast through the
framework at start, and donated buffers so XLA updates weights in
place. The two step programs are built by
``horovod_tpu.models.train_steps``, which ``chip_smoke.py`` imports
too: both scripts run the same program.

Two workloads, one JSON line:

1. **ResNet-50** (the reference's own headline): ImageNet-shaped
   synthetic data, SGD-momentum, batch 256. HBM-roofline-bound — the
   bench reports achieved bandwidth + MFU vs the program's own cap
   (docs/benchmarks.md "MFU roofline study").
2. **Transformer-LM** (compute-bound): 12-layer d=2048 735M-param
   causal LM, seq 2048, bf16, pallas flash attention, chunked
   lm-head cross-entropy, SGD-momentum. Its steady-state training MFU
   is emitted as ``transformer_hvd_train_mfu``.

Baseline: the reference's published example readout is 1656.82 img/s on
16 Pascal GPUs = 103.55 img/s per device (docs/benchmarks.md:29-33).
``vs_baseline`` is img/s-per-chip divided by that number.

Every result names the device it ran on, and a device that is not in
the peak table is an error: a utilization against another chip's peak
is not a measurement. A failed leg raises; nothing is folded into the
JSON as a string.

The collective-path microbenches (bus bandwidth through the full
negotiate->fuse->execute pipeline, N-process scaling efficiency) live
in benchmarks/collective_bench.py — they need a multi-process CPU
world, not the single real chip this script is given.
"""

from __future__ import annotations

import json
import os
from typing import NamedTuple

BASELINE_IMG_PER_SEC_PER_DEVICE = 103.55


class ChipPeak(NamedTuple):
    bf16_flops: float   # dense bf16 FLOP/s per chip
    hbm_bytes: float    # HBM bytes/s per chip
    source: str


# Keyed by ``jax.devices()[0].device_kind`` exactly as JAX reports it
# (the spellings are jax's own: jax/_src/pallas/mosaic/tpu_info.py).
PEAKS = {
    "TPU v4": ChipPeak(
        275e12, 1228e9,
        "Google Cloud TPU documentation, 'TPU v4': 275 TFLOP/s bf16, "
        "1228 GB/s HBM2 per chip"),
    "TPU v5 lite": ChipPeak(
        197e12, 819e9,
        "Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "819 GB/s HBM2e per chip"),
    "TPU v5p": ChipPeak(
        459e12, 2765e9,
        "Google Cloud TPU documentation, 'TPU v5p': 459 TFLOP/s bf16, "
        "2765 GB/s HBM2e per chip"),
    "TPU v6 lite": ChipPeak(
        918e12, 1640e9,
        "Google Cloud TPU documentation, 'TPU v6e': 918 TFLOP/s bf16, "
        "1640 GB/s HBM per chip"),
}


def chip_peak(device_kind: str) -> ChipPeak:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default to some other chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"device kind {device_kind!r} is not in bench.py's peak "
            f"table ({sorted(PEAKS)}); add its published peaks with "
            f"their source before reporting a utilization on it"
        ) from None


def _cost(compiled, key: str) -> float:
    """One positive finite entry of XLA's cost analysis, or raise."""
    import math
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    value = float(ca[key])
    if not math.isfinite(value) or value <= 0:
        raise ValueError(f"cost analysis {key!r} is {value}")
    return value


def _bench_transformer(mesh, peak: ChipPeak) -> dict:
    """Steady-state transformer-LM training MFU with the framework in
    the loop (the compute-bound companion to the ResNet leg). MFU
    convention: model flops = tokens x (6 x matmul-params +
    12 x L x S x d) — the PaLM accounting, full causal square, on the
    same peak-spec basis as the chip's bf16 rating; the causal kernels
    execute ~5% fewer (flops_ratio reports it)."""
    import jax
    import numpy as np

    from horovod_tpu.models import train_steps
    from horovod_tpu.utils.timing import steady_state_sec_per_step

    n_dev = mesh.devices.size
    per_chip_batch = int(os.environ.get("HVD_BENCH_LM_BATCH", "4"))
    seq = int(os.environ.get("HVD_BENCH_LM_SEQ", "2048"))
    batch = per_chip_batch * n_dev
    model = train_steps.bench_lm(seq=seq)
    cfg = model.cfg
    tokens = train_steps.synthetic_tokens(
        0, batch, seq, cfg.vocab_size, mesh)
    tx = train_steps.distributed_sgd()
    params, opt_state = train_steps.lm_train_state(
        model, tx, mesh, tokens)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    d = cfg.embed_dim

    # Compile ONCE via the AOT path and drive every call through the
    # compiled executable (a plain jit call would compile a second
    # copy).
    train = train_steps.lm_train_step(model, tx, mesh).lower(
        params, opt_state, tokens).compile()
    hw_flops = _cost(train, "flops")

    st = {"p": params, "os": opt_state}
    del params, opt_state

    def one_step():
        st["p"], st["os"], loss = train(st["p"], st["os"], tokens)
        return loss

    sec = steady_state_sec_per_step(
        one_step, lambda l: float(l), warmup_steps=5, chunks=4,
        chunk_steps=15)
    tokens_per_step = batch * seq
    # matmul params: everything but the embedding table (a gather);
    # the fp32 lm_head IS a matmul and is included in n_params.
    p_mm = n_params - cfg.vocab_size * d
    model_flops = tokens_per_step * (
        6 * p_mm + 12 * cfg.num_layers * seq * d)
    peak_flops = peak.bf16_flops * n_dev
    return {
        "config": f"L{cfg.num_layers} d{d} S{seq} B{batch} "
                  f"V{cfg.vocab_size}",
        "n_params_M": round(n_params / 1e6, 1),
        "tokens_per_sec": round(tokens_per_step / sec),
        "sec_per_step": round(sec, 4),
        "mfu": round(model_flops / sec / peak_flops, 4),
        "hfu": round(hw_flops / sec / peak_flops, 4),
        "flops_ratio_executed_vs_model": round(
            hw_flops / model_flops, 3),
    }


def _bench_resnet(mesh, peak: ChipPeak) -> dict:
    from horovod_tpu.models import train_steps
    from horovod_tpu.utils.timing import steady_state_sec_per_step

    n_dev = mesh.devices.size
    per_chip_batch = int(os.environ.get("HVD_BENCH_BATCH", "256"))
    batch = per_chip_batch * n_dev
    model = train_steps.bench_resnet()
    images, labels = train_steps.synthetic_images(0, batch, mesh)
    tx = train_steps.distributed_sgd()
    params, batch_stats, opt_state = train_steps.resnet_train_state(
        model, tx, mesh, images)
    train_step = train_steps.resnet_train_step(model, tx, mesh).lower(
        params, batch_stats, opt_state, images, labels).compile()

    # MFU uses analytic MODEL flops: ResNet-50 @224 is 4.089 G MACs
    # per forward image (the widely-quoted "4.09 GFLOPs" is the MACs
    # convention); MFU counts 2 flops per MAC (the PaLM / scaling-book
    # convention, same basis as the chip's peak spec) and 3x forward
    # for the train step. ``flops_ratio`` below reports XLA's own
    # count of the compiled step against it per run.
    model_step_flops = 3 * (2 * 4.089e9) * batch
    hw_step_flops = _cost(train_step, "flops")
    hw_step_bytes = _cost(train_step, "bytes accessed")

    st = {"p": params, "bs": batch_stats, "os": opt_state}
    del params, batch_stats, opt_state

    def one_step():
        st["p"], st["bs"], st["os"], loss = train_step(
            st["p"], st["bs"], st["os"], images, labels)
        return loss

    # Median of chunks: a one-chip machine shares its host's cores, so
    # a single long mean can absorb a bad window.
    sec_per_step = steady_state_sec_per_step(
        one_step, lambda l: float(l), warmup_steps=5, chunks=5,
        chunk_steps=25)

    per_chip = batch / sec_per_step / n_dev
    peak_flops = peak.bf16_flops * n_dev
    hbm_peak = peak.hbm_bytes * n_dev
    mfu = model_step_flops / sec_per_step / peak_flops
    # Roofline readout: this workload is HBM-bound on every chip in
    # PEAKS (arithmetic intensity far below the flops/bandwidth
    # crossover), so the honest optimization metric is achieved
    # bandwidth and MFU relative to the PROGRAM's roofline cap — see
    # docs/benchmarks.md "MFU roofline study".
    cap = min(hw_step_flops / hw_step_bytes * hbm_peak / peak_flops, 1.0)
    roofline_mfu_cap = cap * model_step_flops / hw_step_flops
    return {
        "metric": "resnet50_hvd_train_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMG_PER_SEC_PER_DEVICE, 3),
        "mfu": round(mfu, 4),
        "framework_in_loop": True,
        "n_devices": n_dev,
        "hfu": round(hw_step_flops / sec_per_step / peak_flops, 4),
        "flops_ratio_executed_vs_model": round(
            hw_step_flops / model_step_flops, 3),
        "bytes_accessed_GB": round(hw_step_bytes / 1e9, 2),
        "achieved_hbm_GBps": round(
            hw_step_bytes / sec_per_step / 1e9, 1),
        "hbm_bw_utilization": round(
            hw_step_bytes / sec_per_step / hbm_peak, 4),
        "roofline_mfu_cap": round(roofline_mfu_cap, 4),
        "mfu_vs_roofline": round(mfu / roofline_mfu_cap, 4),
    }


def main() -> None:
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    import horovod_tpu.jax as hvd
    from horovod_tpu import spmd

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peak = chip_peak(devices[0].device_kind)

    hvd.init()
    mesh = spmd.create_mesh({"data": len(devices)}, devices=devices)
    result = _bench_resnet(mesh, peak)
    result["device"] = device
    # Second, compute-bound metric: transformer-LM training MFU (the
    # proof the ResNet number is the workload's roofline, not the
    # framework).
    lm = _bench_transformer(mesh, peak)
    result["transformer_hvd_train_mfu"] = lm["mfu"]
    result["transformer"] = lm
    print(json.dumps(result))
    hvd.shutdown()


if __name__ == "__main__":
    main()
