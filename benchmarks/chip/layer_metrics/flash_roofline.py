"""The flash kernels' share of their roofline: the least time the chip
could take for their FLOPs and bytes (``chipbench.flops``, the causal
half counted once; the larger of FLOPs over the bf16 peak and bytes
over the HBM peak) over their device time in the trace."""
from chipbench import flops, readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    spent = readers.kernel_seconds(ctx, readers.is_pallas_call)
    if not spent:
        return None
    sz = ctx["sz"]
    shape = (sz["per_chip_batch"], sz["heads"], sz["seq"], sz["head_dim"],
             sz["layers"])
    least, bound = flops.roofline_seconds(
        flops.flash_flops_per_step(*shape), flops.flash_bytes_per_step(*shape),
        ctx["peak"].bf16_flops, ctx["peak"].hbm_bytes)
    ctx["notes"].append(f"flash_roofline: {bound}-bound, least "
                        f"{1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
