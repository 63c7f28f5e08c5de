"""Autotune under a real multi-process world: the coordinator tunes,
workers adopt the tuned values through the ResponseList trailer, and the
CSV log records the samples (reference: parameter_manager.cc:64-78
SyncParams; HOROVOD_AUTOTUNE_LOG, parameter_manager.cc:93-99). The
single-process unit tests live in test_autotune.py; this is the
integration leg the reference exercises by running under mpirun."""

import os

import pytest

from tests.test_multiprocess import run_scenario

_MAX_SAMPLES = 3


def test_autotune_two_process_sync_and_log(tmp_path):
    log = str(tmp_path / "autotune.csv")
    run_scenario(
        "autotune", 2, timeout=180.0,
        extra_env={
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_LOG": log,
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "2",
            "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": str(_MAX_SAMPLES),
        })
    assert os.path.exists(log), "coordinator never wrote the CSV log"
    with open(log) as f:
        lines = [ln for ln in f.read().splitlines() if ln]
    assert lines[0] == ("sample,fusion_threshold_mb,cycle_time_ms,"
                        "score_bytes_per_us")
    rows = lines[1:]
    assert len(rows) >= _MAX_SAMPLES, rows
    for row in rows:
        sample, mb, ms, score = row.split(",")
        assert 0.0 <= float(mb) <= 64.0
        assert 1.0 <= float(ms) <= 100.0
        assert float(score) >= 0.0


@pytest.mark.time_limit(270)
def test_autotune_sync_through_hier_controller(tmp_path):
    """Tuned values must reach MIGRATED LEAVES too: with 4 ranks on 2
    fake hosts the ResponseList trailer rides the local root's relay,
    and the adoption assertions inside scenario_autotune run on every
    tier of the hierarchy."""
    log = str(tmp_path / "autotune_hier.csv")
    run_scenario(
        "autotune", 4, timeout=240.0,
        extra_env={
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_LOG": log,
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "2",
            "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": str(_MAX_SAMPLES),
        },
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})
    assert os.path.exists(log)
