"""Operations and bytes of the Kimi delta attention rule's kernels
(``horovod_tpu/parallel/kda.py``), from shapes: beside ``flops.py`` and
``gdn_flops.py``, for the family ``ling3flash_lm``. Nothing here is
taken from the program: no chunk, no sub-block, no level.

**The count is of the recurrence, not of a chunked algorithm.** A head
carries a state of ``Dk x Dv`` entries; a position does, an entry: the
decay (1: a multiply an entry whether the factor is a head's or a
row's), ``S^T k`` (a multiply and an add: 2), the rank-one update (2)
and ``S^T q`` (2): 7, as the scalar rule's. The chunked form's own
products are several times that (the decayed products of every level of
the chunk's triangle, the inverse's products of ``C^3``): a share of
this count's roofline reads that much lower than the MXU's own
utilisation inside the kernels, and is the same yardstick whatever
chunk a kernel picks.

A training step runs the forward kernel once a layer (the recomputed
block keeps its outputs) and the backward kernel once, which computes
the chunk again and then both gradients of every product: four
forwards' worth.

**What differs from the scalar rule is the traffic**: the decay is a
float32 vector of ``Dk`` a position and head, as wide as q and k and
twice their bytes, and so is its gradient.
"""

from __future__ import annotations

OPS_PER_ENTRY = 7
# forward; the backward kernel's recomputed forward; the two gradients
FORWARDS_PER_STEP = 1 + 1 + 2


def rule_forward_ops(batch: int, seq: int, heads: int, key_dim: int,
                     value_dim: int) -> float:
    """One layer's forward alone: what a model FLOP count adds a layer
    (times three for a training step, as for a matmul)."""
    return float(OPS_PER_ENTRY * batch * seq * heads * key_dim * value_dim)


def rule_ops_per_step(batch: int, seq: int, heads: int, key_dim: int,
                      value_dim: int, layers: int) -> float:
    """Operations both kernels need in one training step, all
    ``layers`` of them."""
    return layers * FORWARDS_PER_STEP * rule_forward_ops(
        batch, seq, heads, key_dim, value_dim)


def rule_bytes_per_step(batch: int, seq: int, heads: int, key_dim: int,
                        value_dim: int, layers: int,
                        itemsize: int = 2) -> float:
    """The HBM traffic of the operands and results both kernels are
    **handed**, and nothing a kernel chooses for itself: q, k, v, o and
    their gradients in ``itemsize`` bytes; the log-decay ``g`` (a
    vector of ``key_dim`` a position and head) and ``beta`` and their
    gradients in float32. Forward reads q, k, v, g, beta and writes o;
    backward reads q, k, v, g, beta, do and writes dq, dk, dv, dg,
    dbeta. **Left out**: the state that entered each chunk (how many
    there are is the kernels' own choice, ``gdn_flops.py``)."""
    qk = batch * seq * heads * key_dim * itemsize
    v = batch * seq * heads * value_dim * itemsize
    decay = batch * seq * heads * key_dim * 4
    beta = batch * seq * heads * 4
    forward = 2 * qk + 2 * v + decay + beta
    backward = 4 * qk + 3 * v + 2 * decay + 2 * beta
    return float(layers * (forward + backward))
