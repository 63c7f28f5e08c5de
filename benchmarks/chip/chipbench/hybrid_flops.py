"""Operations and bytes of the flash kernels under a window, over fewer
key-value heads and with a value head of its own size: beside
``flops.py`` (whose counts take one head size and the causal half of a
square), for the family ``phi4flash_lm``.

A *map* is one softmax over one query head's scores. Differential
attention runs two maps a head pair. A map's kernels multiply, per
score the mask allows (``needed_scores``), with key head size ``d`` and
value head size ``dv``:

* forward: q k^T (2 d) and p v (2 dv);
* dq: q k^T again, do v^T (2 dv), ds k (2 d);
* dk/dv: q k^T again, p^T do (2 dv), do v^T (2 dv), ds^T q (2 d).

With ``d == dv`` that is 18 d a score, ``flops.flash_flops_per_step``'s
nine products of the causal half.
"""

from __future__ import annotations

from typing import Optional, Sequence


def needed_scores(seq: int, window: Optional[int] = None) -> int:
    """Scores a causal mask allows in one map over ``seq`` positions:
    row ``i`` sees ``i + 1`` keys, or with a window at most ``window``
    of them (its own and the ``window - 1`` before it)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def forward_flops_per_score(d: int, dv: int) -> int:
    return 2 * (d + dv)


def kernel_flops_per_score(d: int, dv: int) -> int:
    """All three kernels: forward, dq, dk/dv."""
    return 2 * ((d + dv) + (2 * d + dv) + (2 * d + 2 * dv))


def flash_flops_per_step(batch: int, maps: int, seq: int, d: int, dv: int,
                         windows: Sequence[Optional[int]]) -> float:
    """FLOPs the three flash kernels need in one training step:
    ``maps`` maps a layer (query heads, two a differential pair) and
    one entry of ``windows`` a layer that calls them (``None``: the
    whole prefix). The blocks are recomputed but keep the forward
    kernel's outputs, so each kernel runs once a layer and step."""
    return float(batch * maps * kernel_flops_per_score(d, dv)
                 * sum(needed_scores(seq, w) for w in windows))


def flash_bytes_per_step(batch: int, maps: int, kv_maps: int, seq: int,
                         d: int, dv: int, layers: int,
                         itemsize: int = 2) -> float:
    """The least HBM traffic of those kernels: every operand read once
    and every result written once, k and v once a **key-value** head
    (``kv_maps`` a layer: nothing is repeated for the query heads that
    share one). Forward: q, o a query head; k, v a key-value head; the
    row statistic. dq: q, do, dq; k, v; lse and delta. dk/dv: q, do;
    k, v, dk, dv; lse and delta."""
    q_side = (d + dv) + (2 * d + dv) + (d + dv)
    kv_side = (d + dv) + (d + dv) + 2 * (d + dv)
    stats = (1 + 2 + 2) * 4
    per_layer = maps * seq * (q_side * itemsize + stats) \
        + kv_maps * seq * kv_side * itemsize
    return float(batch * layers * per_layer)
