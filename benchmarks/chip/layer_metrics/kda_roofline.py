"""The Kimi delta attention rule's kernels' share of their roofline: the
larger of the recurrence's operations over the MXU's peak and the HBM
traffic of what the kernels are handed over the bandwidth
(``chipbench/kda_flops.py``: both counts come from the cell's shapes
alone, so the least time is the same whatever chunk, sub-block or
triangular inverse the kernels use, and the share reads lower than the
MXU's own utilisation inside them), over the device time of the events
named ``kda_fwd`` and ``kda_bwd``. The note says which bound it is."""
from chipbench import flops, kda_flops, moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    spent = moe_readers.kernel_seconds(ctx, "kda")
    if not spent:
        return None
    sz = ctx["sz"]
    shape = (sz["per_chip_batch"], sz["seq"], sz["heads"], sz["kda_dim"],
             sz["kda_dim"], ctx["family"].kda_layers(sz))
    least, bound = flops.roofline_seconds(
        kda_flops.rule_ops_per_step(*shape),
        kda_flops.rule_bytes_per_step(*shape),
        ctx["peak"].bf16_flops, ctx["peak"].hbm_bytes)
    ctx["notes"].append(f"kda_roofline: {bound}-bound, least "
                        f"{1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
