"""The grouped products' share of their roofline: the least time the
chip could take for the FLOPs and bytes of the assignments the program
counted in the window (``hvd_moe_assignments_total{held="1"}`` a step
counted, ``chipbench/moe_flops.py``) over the device time of the
grouped-product events."""
from chipbench import flops, moe_flops, moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    spent = moe_readers.kernel_seconds(ctx, "grouped")
    rows = moe_readers.held_rows_per_step(ctx)
    if not spent or not rows:
        return None
    sz, family = ctx["sz"], ctx["family"]
    products = moe_flops.PRODUCTS_PER_LAYER
    shape = (sz["d"], sz["expert_mlp"], products)
    least, bound = flops.roofline_seconds(
        moe_flops.grouped_flops(rows, *shape),
        moe_flops.grouped_bytes(rows, family.expert_layers(sz),
                                sz["experts_held"], *shape),
        ctx["peak"].bf16_flops, ctx["peak"].hbm_bytes)
    ctx["notes"].append(
        f"moe_grouped_roofline: {rows:.0f} assignments to held experts a "
        f"step over {family.expert_layers(sz)} expert layers, {products} "
        f"products a layer, {bound}-bound, least {1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
