"""Operations and bytes of the selective scan's kernels
(``horovod_tpu/parallel/ssm_scan.py``), from shapes: beside
``flops.py``, for the family ``phi4flash_lm``.

The recurrence has a decay of its own for every (channel, state) pair,
so it has no matmul form and runs on the **vector unit**; its roofline
is the larger of its HBM traffic over the bandwidth and its vector
operations over the vector unit's peak. One *element* is one (position,
channel, state) triple.

* forward, an element: ``delta A`` (1), its exponential (1), ``a h``
  (1), ``(delta u) B`` (1), their sum (1), ``h C`` (1), into ``y`` (1):
  7;
* backward, an element: the chunk's states again (5: the forward's
  without ``y``), then the reverse recurrence: the decay again (2),
  ``G = dy C + K`` (2), the two products whose sums over channels are
  ``dB`` and ``dC`` (2) and those sums (2), ``G B`` into ``ddelta`` and
  ``du`` (2), ``w = G h a`` (2), ``w A`` into ``ddelta`` (2), ``w
  delta`` into ``dA`` (2), ``K = G a`` (1): 17; 22 in all.

The forward kernel runs once a layer and step (the recomputed block
keeps its output), the backward once.
"""

from __future__ import annotations

FORWARD_OPS = 7
BACKWARD_OPS = 22

# The vector unit's peak is not in the published tables ``peaks.py``
# copies. An upper bound from what is published: a TensorCore issues at
# most four vector operations a cycle (its instruction bundle has four
# vector slots: Norrie et al., "The Design Process for Google's Training
# Chips: TPUv2 and TPUv3", IEEE Micro 2021, which the later cores
# keep), each over one register of 8 x 128 lanes; the clock follows
# from the published matrix peak (four 128 x 128 units, 2 operations a
# cell and cycle: Google Cloud TPU documentation, "TPU v5e"). A share
# of this bound errs low, never high.
VECTOR_SLOTS = 4
REGISTER_LANES = 8 * 128
MATRIX_UNITS = 4
MATRIX_CELLS = 128 * 128


def vector_peak_ops(bf16_flops: float) -> float:
    """float32 vector operations a second the chip can issue at most,
    from its published matrix peak (module comment)."""
    clock = bf16_flops / (MATRIX_UNITS * MATRIX_CELLS * 2)
    return VECTOR_SLOTS * REGISTER_LANES * clock


def scan_elements(batch: int, seq: int, channels: int, states: int) -> int:
    return batch * seq * channels * states


def scan_ops_per_step(batch: int, seq: int, channels: int, states: int,
                      layers: int) -> float:
    """Vector operations both kernels need in one training step, all
    ``layers`` scans."""
    return float(layers * (FORWARD_OPS + BACKWARD_OPS)
                 * scan_elements(batch, seq, channels, states))


def scan_forward_ops(batch: int, seq: int, channels: int,
                     states: int) -> float:
    """One scan's forward alone: what a model FLOP count adds a layer
    (times three for a training step, as for a matmul)."""
    return float(FORWARD_OPS * scan_elements(batch, seq, channels, states))


def scan_bytes_per_step(batch: int, seq: int, channels: int, states: int,
                        layers: int, chunk: int) -> float:
    """The least HBM traffic of both kernels as they are handed their
    operands (float32): forward reads ``u`` and ``delta`` and writes
    ``y``; backward reads ``u``, ``delta`` and ``dy`` and writes ``du``
    and ``ddelta``; both pass the state that entered each chunk
    (``channels x states`` a chunk) and ``B`` and ``C`` (``2 states`` a
    position, once a kernel)."""
    timed = batch * seq * channels * 4
    entering = batch * -(-seq // chunk) * channels * states * 4
    bc = batch * seq * 2 * states * 4
    return float(layers * ((3 + 5) * timed + 2 * entering + 2 * bc))
