"""Autotuner: steer fusion threshold × cycle time for throughput.

(reference: horovod/common/parameter_manager.{h,cc} — joint Bayesian
optimization of fusion-threshold-MB ∈ [0,64] × cycle-time-ms ∈ [1,100],
parameter_manager.h:169-207; score = bytes/µs over samples of
``steps_per_sample`` cycles with median-of-k smoothing,
parameter_manager.cc:28-31,145-171; warmup discard; rank-0 tunes and
the tuned values ride to workers — in the reference via a custom MPI
struct broadcast (cc:64-78), here inside the ResponseList trailer,
which every rank already receives every cycle.)

Enabled with ``HOROVOD_AUTOTUNE=1``; progress optionally logged to
``HOROVOD_AUTOTUNE_LOG`` as CSV.
"""

from __future__ import annotations

import time

import numpy as np

from horovod_tpu.common import logging as hlog
from horovod_tpu.common import wire_dtype as _wd
from horovod_tpu.optim.bayesian_optimization import BayesianOptimization

_MB = 1024 * 1024

# Size buckets for the per-bucket (algorithm, wire dtype) table, by
# UNCOMPRESSED fused-batch bytes: latency-bound small ops, the
# mid-range, and bandwidth-bound large ops. Same shape as the ring
# threshold's reasoning — different sizes want different planes.
BUCKET_BOUNDS = (64 * 1024, 1 << 20)


def bucket_of(nbytes: int) -> int:
    for i, bound in enumerate(BUCKET_BOUNDS):
        if nbytes < bound:
            return i
    return len(BUCKET_BOUNDS)


class _BucketTuner:
    """Measured grid sweep over (ALG_*, WIRE_* cap) combos, one size
    bucket at a time — the discrete half of the autotuner. The
    continuous (fusion threshold, cycle time) pair stays Bayesian;
    these grids are tiny (<= 8 combos) and categorical, so measuring
    every point and keeping the argmax IS the optimal policy — the
    90%-of-best acceptance bar holds by construction, modulo noise
    the median-of-3 smoothing absorbs.

    A bucket that sees no traffic for two consecutive sample windows
    is skipped (keeps the default plan) so an idle bucket can never
    stall convergence. Each combo is measured in TWO interleaved
    passes and scored by the MAX of its samples: scheduler throttle
    bursts (multi-second on shared CI hosts) only ever DEFLATE a
    throughput sample, so the per-combo upper envelope is the robust
    comparator — one pass with adjacent combos landing in different
    throttle phases mis-ranks them."""

    _IDLE_LIMIT = 2
    _PASSES = 2

    def __init__(self, combos, nbuckets: int):
        self._combos = list(combos)
        self._nbuckets = nbuckets
        self._bucket = 0
        self._ci = 0
        self._pass = 0
        self._scores = {}  # (bucket, combo_idx) -> max sample score
        self._idle = 0
        self.done = nbuckets == 0 or len(self._combos) < 2
        self.plan = [(_wd.ALG_DEFAULT, None)] * nbuckets
        # Bumped on every active-combo move (advance, bucket change,
        # settle): the coordinator watches it and force-evicts cached
        # verdicts stamped under the previous plan.
        self.revision = 0

    @property
    def bucket(self) -> int:
        return self._bucket

    def current_combo(self):
        return self._combos[self._ci]

    def feed(self, score: float, bucket_traffic: int,
             total_traffic: int = -1) -> None:
        """One median-of-3 sample measured under the current combo;
        ``bucket_traffic`` is the bytes the bucket under test moved
        during the window (zero = the measurement says nothing about
        this combo). ``total_traffic`` across ALL buckets separates
        "this bucket is idle while the job runs" (a strike toward
        skipping it) from a GLOBAL lull (eval phase, dataloader
        stall — retry without penalty, or a two-window pause would
        permanently forfeit a hot bucket's tuning)."""
        if self.done:
            return
        if bucket_traffic <= 0:
            if total_traffic == 0:
                return  # global pause: says nothing about the bucket
            self._idle += 1
            if self._idle >= self._IDLE_LIMIT:
                self._next_bucket(keep_default=True)
            return
        self._idle = 0
        key = (self._bucket, self._ci)
        self._scores[key] = max(score, self._scores.get(
            key, float("-inf")))
        self._ci += 1
        self.revision += 1
        if self._ci >= len(self._combos):
            self._ci = 0
            self._pass += 1
            if self._pass >= self._PASSES:
                self._next_bucket(keep_default=False)

    def _next_bucket(self, keep_default: bool) -> None:
        self.revision += 1
        if not keep_default:
            best = max(range(len(self._combos)),
                       key=lambda i: self._scores.get(
                           (self._bucket, i), float("-inf")))
            self.plan[self._bucket] = self._combos[best]
        self._bucket += 1
        self._ci = 0
        self._pass = 0
        self._idle = 0
        if self._bucket >= self._nbuckets:
            self.done = True

    def describe(self) -> str:
        return " ".join(
            f"b{i}={_wd.ALG_NAMES[a]}/"
            + ("-" if w is None else _wd.WIRE_NAMES[w])
            for i, (a, w) in enumerate(self.plan))


class _OverlapTuner:
    """Measured sweep over overlap bucket counts — the second discrete
    grid phase, run AFTER the wire sweep and BEFORE the Bayesian phase
    (speculation must stay live while it measures: the overlap tier IS
    a property of the fused speculative regime). Same protocol as
    _BucketTuner: each candidate scored by the MAX of its samples over
    two interleaved passes (throttle bursts only deflate throughput),
    argmax wins. The active candidate rides the ResponseList trailer so
    every rank splits its submissions identically; transient mismatch
    during adoption merely degrades those cycles to the classic path."""

    _PASSES = 2

    def __init__(self, candidates):
        self._candidates = list(candidates)
        self._ci = 0
        self._pass = 0
        self._scores = [float("-inf")] * len(self._candidates)
        self.done = len(self._candidates) < 2
        self.choice = self._candidates[0] if self._candidates else 0

    def current(self) -> int:
        return self._candidates[self._ci]

    def feed(self, score: float, traffic: int) -> None:
        if self.done or traffic <= 0:
            return  # a lull says nothing about the candidate
        self._scores[self._ci] = max(score, self._scores[self._ci])
        self._ci += 1
        if self._ci >= len(self._candidates):
            self._ci = 0
            self._pass += 1
            if self._pass >= self._PASSES:
                best = max(range(len(self._candidates)),
                           key=lambda i: self._scores[i])
                self.choice = self._candidates[best]
                self.done = True


class ParameterManager:
    def __init__(self, config, controller):
        self._is_coordinator = controller.rank == 0
        self._warmup_remaining = config.autotune_warmup_samples
        self._steps_per_sample = config.autotune_steps_per_sample
        self._max_samples = config.autotune_bayes_opt_max_samples
        self._bo = BayesianOptimization(
            bounds=[(0.0, 64.0), (1.0, 100.0)],  # MB, ms
            alpha=config.autotune_gaussian_process_noise)
        self._log_path = config.autotune_log
        if self._log_path and self._is_coordinator:
            with open(self._log_path, "w") as f:
                f.write("sample,fusion_threshold_mb,cycle_time_ms,"
                        "score_bytes_per_us\n")

        self._current = np.asarray(
            [config.fusion_threshold_bytes / _MB, config.cycle_time_ms])
        self._tuning = self._is_coordinator
        self._samples_taken = 0
        # Per-bucket (algorithm, wire-dtype cap) table the coordinator
        # stamps fused responses with (Runtime._stamp_wire_plan). The
        # discrete grid phase (armed via configure_wire) runs before
        # the continuous BO phase; until then — and on workers, who
        # never stamp — the table is all-default.
        nb = len(BUCKET_BOUNDS) + 1
        self._bucket_plan = [(_wd.ALG_DEFAULT, None)] * nb
        self._bucket_tuner = None
        # Overlap bucket-count grid (configure_overlap): None until
        # armed; workers adopt the coordinator's active/settled value
        # from the ResponseList trailer (apply_synced).
        self._overlap_tuner = None
        self._overlap_current = None
        self._bucket_bytes = [0] * nb
        self._bucket_mark = [0] * nb
        # per-sample accumulation
        self._cycle_count = 0
        self._bytes_acc = 0
        self._t0 = time.monotonic()
        # median-of-k smoothing (reference: median of scores, cc:145-171)
        self._scores = []

    # -- wire plan (algorithm x dtype per size bucket) -------------------
    def configure_wire(self, proposed_wire: int, multi_host: bool,
                       world_size: int, shm_enabled: bool = True,
                       ring_allowed: bool = True) -> None:
        """Arm the discrete grid phase (coordinator only). Algorithm
        candidates follow topology AND configuration feasibility
        (ring needs >= 3 ranks and must not be explicitly disabled;
        two-level needs a multi-host world with the shm plane on,
        because a stamped combo whose plane cannot engage would just
        measure default routing twice under a misleading name); wire
        candidates are
        every dtype AT OR BELOW this world's proposal — the tuner
        explores by CAPPING the negotiated verdict, so it can never
        compress harder than the operator asked (numerics-safe)."""
        if not self._is_coordinator or not self._tuning:
            return
        algs = [_wd.ALG_DEFAULT]
        if world_size >= 3 and ring_allowed:
            algs.append(_wd.ALG_RING)
        if multi_host and shm_enabled:
            algs.append(_wd.ALG_TWOLEVEL)
        wires = [w for w in (_wd.WIRE_NONE, _wd.WIRE_BF16,
                             _wd.WIRE_FP16, _wd.WIRE_INT8)
                 if w <= proposed_wire]
        combos = [(a, w) for a in algs for w in wires]
        if len(combos) > 1:
            self._bucket_tuner = _BucketTuner(
                combos, len(BUCKET_BOUNDS) + 1)

    def configure_overlap(self, armed: bool) -> None:
        """Add the overlap bucket count to the discrete grid
        (coordinator only, and only when the overlap tier can engage):
        candidates 0 (off), 2, 4, 8 buckets, measured after the wire
        sweep settles and scored by the same bytes/µs stream."""
        if not armed or not self._is_coordinator or not self._tuning:
            return
        self._overlap_tuner = _OverlapTuner([0, 2, 4, 8])

    def overlap_buckets(self):
        """The bucket count the overlap dispatcher should use right
        now, or None when the tuner never armed (static knobs rule).
        Coordinator: the candidate under measurement, then the settled
        argmax. Workers: the value adopted from the trailer."""
        t = self._overlap_tuner
        if t is not None:
            if t.done:
                return t.choice
            # Only measure once the wire sweep settled: both grids
            # share the score stream, and interleaving them would
            # attribute one dimension's effect to the other.
            wt = self._bucket_tuner
            if wt is None or wt.done:
                return t.current()
            return None
        return self._overlap_current

    @property
    def tuned_overlap_buckets(self) -> int:
        """Trailer value the coordinator stamps each cycle: the active
        candidate/settled choice, or -1 (no verdict) while unarmed."""
        v = self.overlap_buckets() if self._is_coordinator else None
        return -1 if v is None else int(v)

    def plan(self, nbytes: int):
        """-> (ALG_* code, wire cap or None) for one fused batch —
        the coordinator's stamping policy (Runtime._stamp_wire_plan).
        While the grid phase runs, the bucket under test answers with
        the combo being measured; everything else follows the
        settled table."""
        b = bucket_of(nbytes)
        self._bucket_bytes[b] += nbytes
        t = self._bucket_tuner
        if t is not None and not t.done:
            if b == t.bucket:
                return t.current_combo()
            if b < t.bucket:
                # Already-settled buckets stamp their measured argmax
                # IMMEDIATELY: later buckets must be scored in the
                # regime the final plan will deploy, and the settled
                # combo's speedup starts paying during the rest of
                # the sweep instead of after it.
                return t.plan[b]
        return self._bucket_plan[b]

    def bucket_plan(self):
        """The settled per-bucket (algorithm, wire cap) table —
        benchmark/test surface."""
        return list(self._bucket_plan)

    @property
    def plan_revision(self) -> int:
        """Monotone counter of active-plan moves (combo advances +
        the final convergence), watched by the coordinator to
        force-evict cached verdicts stamped under a superseded plan —
        the mechanism that lets autotune and the response cache
        coexist."""
        rev = self._bucket_tuner.revision \
            if self._bucket_tuner is not None else 0
        # +1 at convergence: the last eviction resets spec-denial
        # slates (epoch move), so the fused speculative cycle
        # re-engages for the tuned steady state.
        return rev + (0 if self._tuning else 1)

    @property
    def spec_safe(self) -> bool:
        """May the fused speculative cycle run? Yes on workers (their
        bids are opportunistic by design), yes through the discrete
        grid phase (combo scores must measure the DEPLOYMENT regime,
        spec cycle included — its parameters are frozen), yes after
        convergence; no only while the Bayesian phase steers
        fusion/cycle values through full-response trailers that
        speculative cycles would starve."""
        if not self._is_coordinator or not self._tuning:
            return True
        t = self._bucket_tuner
        if t is not None and not t.done:
            return True
        # The overlap grid ALSO needs live speculation: its candidates
        # are properties of the fused speculative regime.
        ot = self._overlap_tuner
        return ot is not None and not ot.done

    # -- values consumed by the runtime ---------------------------------
    @property
    def tuning(self) -> bool:
        """True while the coordinator's optimizer is still exploring;
        False once converged (or on workers, which never tune). The
        public convergence probe for benchmarks/tests."""
        return self._tuning

    def fusion_threshold_bytes(self) -> int:
        return int(self._current[0] * _MB)

    def cycle_time_ms(self) -> float:
        return float(self._current[1])

    def apply_synced(self, fusion_threshold_bytes: int,
                     cycle_time_ms: float,
                     overlap_buckets: int = -1) -> None:
        """Workers adopt the coordinator's tuned values (reference:
        SyncParams, parameter_manager.cc:64-78). The untuned-trailer
        sentinel is cycle_time_ms == 0: real tuned cycle times are
        bounded >= 1 ms, while a FUSION threshold of 0 MB is a
        legitimate tuned value (fusion off) and must still be adopted.
        ``overlap_buckets`` uses -1 as its sentinel (0 = tuned OFF is
        a legitimate verdict)."""
        if not self._is_coordinator and cycle_time_ms > 0:
            self._current = np.asarray(
                [fusion_threshold_bytes / _MB, cycle_time_ms])
        if not self._is_coordinator and overlap_buckets >= 0:
            self._overlap_current = overlap_buckets

    # -- sampling --------------------------------------------------------
    def on_cycle(self, nbytes: int) -> None:
        """Called by the background loop once per cycle with the bytes
        processed (reference: parameter_manager.cc Update)."""
        if not self._tuning:
            return
        self._bytes_acc += nbytes
        self._cycle_count += 1
        if self._cycle_count < self._steps_per_sample:
            return
        elapsed_us = (time.monotonic() - self._t0) * 1e6
        score = self._bytes_acc / max(elapsed_us, 1.0)
        self._cycle_count = 0
        self._bytes_acc = 0
        self._t0 = time.monotonic()

        if self._warmup_remaining > 0:
            self._warmup_remaining -= 1
            return

        self._scores.append(score)
        if len(self._scores) < 3:
            return
        sample_score = float(np.median(self._scores))
        self._scores = []

        # Phase 1 — discrete grid: route median samples to the bucket
        # tuner until every (algorithm, wire) combo of every
        # traffic-bearing bucket has been measured; the continuous BO
        # phase below then runs against the SETTLED table.
        t = self._bucket_tuner
        if t is not None and not t.done:
            b = t.bucket
            traffic = self._bucket_bytes[b] - self._bucket_mark[b]
            total = sum(self._bucket_bytes) - sum(self._bucket_mark)
            self._bucket_mark = list(self._bucket_bytes)
            t.feed(sample_score, traffic, total)
            if t.done:
                self._bucket_plan = list(t.plan)
                hlog.info("autotune wire plan settled: "
                          + t.describe())
            return

        # Phase 2 — overlap bucket-count grid (speculation stays live;
        # see spec_safe). Scored by total traffic: bucketing reshapes
        # every allreduce, not one size bucket.
        ot = self._overlap_tuner
        if ot is not None and not ot.done:
            total = sum(self._bucket_bytes) - sum(self._bucket_mark)
            self._bucket_mark = list(self._bucket_bytes)
            ot.feed(sample_score, total)
            if ot.done:
                hlog.info(f"autotune overlap bucket count settled: "
                          f"{ot.choice}")
            return

        self._samples_taken += 1
        self._bo.add_sample(self._current.copy(), sample_score)
        if self._log_path:
            with open(self._log_path, "a") as f:
                f.write(f"{self._samples_taken},{self._current[0]:.3f},"
                        f"{self._current[1]:.3f},{sample_score:.6f}\n")
        if self._samples_taken >= self._max_samples:
            best, best_score = self._bo.best()
            if best is not None:
                self._current = np.asarray(best)
            self._tuning = False
            hlog.info(
                f"autotune converged: fusion_threshold="
                f"{self._current[0]:.1f} MB cycle_time="
                f"{self._current[1]:.1f} ms (score {best_score:.3f} B/µs)")
            return
        self._current = np.clip(self._bo.next_sample(),
                                [0.0, 1.0], [64.0, 100.0])
