"""Operations and bytes computed from shapes: the benchmark's yardstick.

No share is reported against a count that is not derived here.
"""

from __future__ import annotations

# ResNet-50 at 224x224: 4.089 G multiply-accumulates per forward image
# (the widely quoted "4.09 GFLOPs" counts MACs). Two FLOPs per MAC, and
# a training step is three forward passes' worth (forward, and the
# backward's two products per layer).
RESNET50_FORWARD_MACS = 4.089e9


def lm_param_count(vocab: int, layers: int, d: int, mlp: int) -> int:
    """Parameters of ``TransformerLM`` with no biases and an untied
    head: per block q, k, v, o (4 d^2), up and down (2 d mlp) and two
    norm scales; the embedding, the head and the last norm."""
    return layers * (4 * d * d + 2 * d * mlp + 2 * d) \
        + 2 * vocab * d + d


def lm_matmul_params(vocab: int, layers: int, d: int, mlp: int) -> int:
    """Everything a token multiplies: all but the embedding table (a
    gather)."""
    return lm_param_count(vocab, layers, d, mlp) - vocab * d


def lm_flops_per_token(vocab: int, layers: int, d: int, mlp: int,
                       seq: int) -> float:
    """The PaLM count: 6 per matmul parameter plus 12 L S d for
    attention (the full causal square, not the half executed)."""
    return 6.0 * lm_matmul_params(vocab, layers, d, mlp) \
        + 12.0 * layers * seq * d


def lm_flops_per_step(vocab, layers, d, mlp, seq, batch) -> float:
    return batch * seq * lm_flops_per_token(vocab, layers, d, mlp, seq)


def resnet50_flops_per_image() -> float:
    return 3 * 2 * RESNET50_FORWARD_MACS


def flash_flops_per_step(batch: int, heads: int, seq: int, head_dim: int,
                         layers: int) -> float:
    """FLOPs the three causal flash kernels need in one training step.

    One product of [S, D] by [D, S] (or its like) over the full square
    is 2 S^2 D FLOPs a head. The forward kernel has two (q k^T, p v);
    the dq kernel three (q k^T again, do v^T, ds k); the dk/dv kernel
    four (q k^T again, p^T do, do v^T, ds^T q): nine. The causal half
    is counted once: half of the square is masked and need not be
    computed, so each product counts S^2 D, not 2 S^2 D.
    """
    per_product = seq * seq * head_dim            # causal half of 2 S^2 D
    return 9.0 * per_product * batch * heads * layers


def flash_bytes_per_step(batch: int, heads: int, seq: int, head_dim: int,
                         layers: int, itemsize: int = 2) -> float:
    """The least HBM traffic of the three kernels in one step: every
    operand read once and every result written once. Forward reads q,
    k, v and writes o (4 tensors of [S, D]) and the row statistic lse
    ([S] f32); dq reads q, k, v, do and writes dq (5) and reads lse
    and delta; dk/dv reads q, k, v, do and writes dk, dv (6) and reads
    lse and delta."""
    tensor = seq * head_dim * itemsize
    stat = seq * 4
    per_head = (4 + 5 + 6) * tensor + (1 + 2 + 2) * stat
    return float(per_head) * batch * heads * layers


def roofline_seconds(flops: float, bytes_: float, peak_flops: float,
                     peak_bytes: float):
    """(least seconds the chip could take, which bound it is)."""
    t_flops = flops / peak_flops
    t_bytes = bytes_ / peak_bytes
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
