"""The flash kernels' share of their roofline under latent attention
whose two head sizes differ: the causal half's scores a head, q and k
at the score head's size (``nope + rope``), v and o at the value
head's (``chipbench/hybrid_flops.py``; every head has keys and values
of its own), every attention layer, over the device time of the events
named ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``."""
from chipbench import flops, hybrid_flops, moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    sz = ctx["sz"]
    if "nope" not in sz or "v_dim" not in sz:
        return None
    spent = moe_readers.kernel_seconds(ctx, "flash")
    if not spent:
        return None
    layers = ctx["family"].attention_layers(sz)
    d, dv = sz["nope"] + sz["rope"], sz["v_dim"]
    least, bound = flops.roofline_seconds(
        hybrid_flops.flash_flops_per_step(
            sz["per_chip_batch"], sz["heads"], sz["seq"], d, dv,
            [None] * layers),
        hybrid_flops.flash_bytes_per_step(
            sz["per_chip_batch"], sz["heads"], sz["heads"], sz["seq"],
            d, dv, layers),
        ctx["peak"].bf16_flops, ctx["peak"].hbm_bytes)
    ctx["notes"].append(f"latent_flash_roofline: {bound}-bound, least "
                        f"{1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
