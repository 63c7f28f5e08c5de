"""The flash kernels' share of their roofline at the latent attention's
shape (20 heads of 256, every attention layer, the multi-token module's
among them): ``flops.flash_flops_per_step`` and
``flops.flash_bytes_per_step`` over the device time of the kernels
matched by name. The blocks are recomputed but keep the forward
kernel's outputs, so a step runs each kernel once a layer."""
from chipbench import flops, moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    spent = moe_readers.kernel_seconds(ctx, "flash")
    if not spent:
        return None
    sz, family = ctx["sz"], ctx["family"]
    shape = (sz["per_chip_batch"], sz["heads"], sz["seq"], sz["head_dim"],
             family.attention_layers(sz))
    least, bound = flops.roofline_seconds(
        flops.flash_flops_per_step(*shape), flops.flash_bytes_per_step(*shape),
        ctx["peak"].bf16_flops, ctx["peak"].hbm_bytes)
    ctx["notes"].append(f"mla_flash_roofline: {bound}-bound, least "
                        f"{1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
