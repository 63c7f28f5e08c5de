"""Operations and bytes of an expert layer's grouped matrix products,
from the assignments the program counted: beside ``flops.py``, for the
family ``glm_moe_lm``.

A grouped product multiplies the ``rows`` assignments that went to the
held experts, sorted by expert, each group by its own expert's kernel.
One expert's SwiGLU is three such products forward (gate, up, down).
The backward pass makes two of each (the rows' gradient and the
kernel's), and the recomputed block runs the three forward ones again:
twelve a layer and step.
Whichever of them it is, a product of ``rows`` x ``d`` x ``width``
multiplies 2 rows d width and touches one [rows, d], one [rows, width]
and the experts' [held, d, width] once each.
"""

from __future__ import annotations

# gate, up, down forward; each one's two gradients; and the forward
# three again, since the program recomputes every block in its backward
PRODUCTS_PER_LAYER = 3 + 6 + 3


def grouped_flops(rows: float, d: int, width: int, products: int) -> float:
    """FLOPs of ``products`` grouped products over ``rows`` assignments
    in all (every expert layer's, summed): only rows that reached a
    held expert multiply."""
    return 2.0 * rows * d * width * products


def grouped_bytes(rows: float, layers: int, held: int, d: int, width: int,
                  products: int, itemsize: int = 2) -> float:
    """The least HBM traffic of those products: the rows' two sides
    read or written once, and each layer's held kernels once a product
    (``rows`` is the sum over ``layers`` expert layers)."""
    return float(itemsize) * products * (
        rows * (d + width) + layers * held * d * width)


def expected_rows_per_layer(tokens: int, top_k: int, held: int,
                            experts: int) -> float:
    """Assignments to held experts a layer sees by expectation, under a
    router that spreads its choices evenly."""
    return tokens * top_k * held / experts
