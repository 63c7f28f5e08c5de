"""Operations and bytes of the gated delta rule's kernels
(``horovod_tpu/parallel/gated_delta.py``), from shapes: beside
``flops.py``, for the family ``qwen3next_lm``.

**The count is of the recurrence, not of a chunked algorithm.** A
value head carries a state of ``Dk x Dv`` entries; a position does, an
entry: the decay (1), ``S^T k`` (a multiply and an add: 2), the
rank-one update (2) and ``S^T q`` (2): 7. The yardstick then reads the
same work whatever chunk length or triangular inverse a kernel uses,
as ``flops.flash_flops_per_step`` counts the causal half once whatever
the tiles execute. The chunked form's own products are more: with
heads of 128 a position and value head costs 245,760 operations forward
at a chunk of 64 and 622,592 at the kernels' 128
(``gated_delta.chunk_flops``: the shared ``K K^T`` and ``Q K^T``, the
inverse's products of ``C^3``, ``W``, ``U``, ``W S``, ``Q S``, ``P V'``
and the state's update) against the recurrence's 114,688: 2.1 and 5.4
times. A share of this count's roofline reads that much lower than the
MXU's own utilisation inside the kernels.

A training step runs the forward kernel once a layer (the recomputed
block keeps its outputs) and the backward kernel once, which computes
the chunk again and then both gradients of every product: four
forwards' worth.

The products are matrix products (the kernels run them on the MXU), so
the compute bound is the MXU's peak; the roofline is the larger of
that and the kernels' HBM traffic over the bandwidth.
"""

from __future__ import annotations

OPS_PER_ENTRY = 7
# forward; the backward kernel's recomputed forward; the two gradients
FORWARDS_PER_STEP = 1 + 1 + 2


def rule_forward_ops(batch: int, seq: int, value_heads: int, key_dim: int,
                     value_dim: int) -> float:
    """One layer's forward alone: what a model FLOP count adds a layer
    (times three for a training step, as for a matmul)."""
    return float(OPS_PER_ENTRY * batch * seq * value_heads
                 * key_dim * value_dim)


def rule_ops_per_step(batch: int, seq: int, value_heads: int, key_dim: int,
                      value_dim: int, layers: int) -> float:
    """Operations both kernels need in one training step, all
    ``layers`` of them."""
    return layers * FORWARDS_PER_STEP * rule_forward_ops(
        batch, seq, value_heads, key_dim, value_dim)


def rule_bytes_per_step(batch: int, seq: int, key_heads: int,
                        value_heads: int, key_dim: int, value_dim: int,
                        layers: int, itemsize: int = 2) -> float:
    """The HBM traffic of the operands and results both kernels are
    **handed**, and nothing a kernel chooses for itself: q, k
    (``key_heads`` of them, never repeated), v, o and their gradients
    in ``itemsize`` bytes; ``G`` and ``beta`` and their gradients in
    float32. Forward reads q, k, v, G, beta and writes o; backward
    reads q, k, v, G, beta, do and writes dq, dk, dv, dG, dbeta.

    **Left out**: the state that entered each chunk (``Dk x Dv``
    float32 a value head and chunk, written by the forward and read by
    the backward). How many there are is the kernels' own choice (the
    chunk length, or keeping one every few chunks), so counting them
    would move the yardstick with the implementation: a shorter chunk
    would read a higher share for a slower kernel. At the model's shape
    they are 268 MB a layer written and read at a chunk of 128, half
    again what this count holds (1,086 MB a layer)."""
    qk = batch * seq * key_heads * key_dim * itemsize
    v = batch * seq * value_heads * value_dim * itemsize
    gate = batch * seq * value_heads * 4
    forward = 2 * qk + 2 * v + 2 * gate
    backward = 4 * qk + 3 * v + 4 * gate
    return float(layers * (forward + backward))
