"""Operations and bytes of the flash kernels in a model whose layers
differ in their window: beside ``hybrid_flops.py`` (whose counts take
the windows a layer at a time and k and v by key-value head), for the
family ``smallthinker_lm``.

A windowed layer's query at ``t`` sees the keys ``t - window + 1 .. t``
(its own counted), a full layer's the whole prefix:
``hybrid_flops.needed_scores``. At a row of 16,384 and a window of
4,096 a band keeps 58,722,304 of the causal half's 134,225,920 scores a
head, so three bands and one half are 310,392,832: 2.31 halves.
"""

from __future__ import annotations

from chipbench import hybrid_flops


def windows(sz: dict) -> list:
    """The window of each kept layer's flash call, ``None`` where the
    published ``sliding_window_layout`` gives it the whole prefix."""
    return [sz["window"] if sz["window_layout"][i] else None
            for i in sz["kept"]]


def needed_scores_per_head(sz: dict) -> int:
    """Scores a query head's maps need in one row, all kept layers."""
    return sum(hybrid_flops.needed_scores(sz["seq"], w) for w in windows(sz))


def flash_flops_per_step(sz: dict) -> float:
    """What the three kernels multiply in a step: nine products of a
    head of ``head_dim`` a needed score (18 d), a query head."""
    hd = sz["head_dim"]
    return hybrid_flops.flash_flops_per_step(
        sz["per_chip_batch"], sz["heads"], sz["seq"], hd, hd, windows(sz))


def flash_bytes_per_step(sz: dict) -> float:
    """Their least HBM traffic: q, o, do, dq a query head, k, v, dk, dv
    a **key-value** head, whatever the window."""
    hd = sz["head_dim"]
    return hybrid_flops.flash_bytes_per_step(
        sz["per_chip_batch"], sz["heads"], sz["kv_heads"], sz["seq"], hd, hd,
        len(sz["kept"]))


def attention_flops_per_token(sz: dict) -> float:
    """A token's share of attention in a training step by the model's
    count: two products forward (q k^T, p v) over the scores the masks
    allow, three forwards' worth."""
    return 3.0 * sz["heads"] * 2 * 2 * sz["head_dim"] \
        * needed_scores_per_head(sz) / sz["seq"]
