"""The flash kernels' share of their roofline in the
decoder-hybrid-decoder: needed scores summed over the window layer
(its band), the full layer and the cross layers (the causal half), two
maps a head pair, key head 64 and value head 128
(``chipbench/hybrid_flops.py``), over the device time of the events
named ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``."""
from chipbench import flops, hybrid_flops, moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    spent = moe_readers.kernel_seconds(ctx, "flash")
    if not spent:
        return None
    sz, family = ctx["sz"], ctx["family"]
    windows = family.attention_windows(sz)
    least, bound = flops.roofline_seconds(
        hybrid_flops.flash_flops_per_step(
            sz["per_chip_batch"], sz["heads"], sz["seq"], sz["head_dim"],
            2 * sz["head_dim"], windows),
        hybrid_flops.flash_bytes_per_step(
            sz["per_chip_batch"], sz["heads"], sz["kv_heads"], sz["seq"],
            sz["head_dim"], 2 * sz["head_dim"], len(windows)),
        ctx["peak"].bf16_flops, ctx["peak"].hbm_bytes)
    ctx["notes"].append(f"hybrid_flash_roofline: {bound}-bound, least "
                        f"{1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
