"""The gated delta rule's kernels' share of their roofline: the larger
of the recurrence's operations over the MXU's peak and the HBM traffic
of what the kernels are handed over the bandwidth
(``chipbench/gdn_flops.py``: both counts come from the cell's shapes
alone, so the least time is the same whatever chunk length or
triangular inverse the kernels use, and the share reads lower than the
MXU's own utilisation inside them), over the device time of the events
named ``gdn_fwd`` and ``gdn_bwd``. The note says which bound it is."""
from chipbench import flops, gdn_flops, moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    spent = moe_readers.kernel_seconds(ctx, "gdn")
    if not spent:
        return None
    sz, family = ctx["sz"], ctx["family"]
    layers = family.delta_layers(sz)
    least, bound = flops.roofline_seconds(
        gdn_flops.rule_ops_per_step(
            sz["per_chip_batch"], sz["seq"], sz["value_heads"],
            sz["key_dim"], sz["value_dim"], layers),
        gdn_flops.rule_bytes_per_step(
            sz["per_chip_batch"], sz["seq"], sz["key_heads"],
            sz["value_heads"], sz["key_dim"], sz["value_dim"], layers),
        ctx["peak"].bf16_flops, ctx["peak"].hbm_bytes)
    ctx["notes"].append(f"gdn_roofline: {bound}-bound, least "
                        f"{1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
