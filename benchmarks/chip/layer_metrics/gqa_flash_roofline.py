"""The flash kernels' share of their roofline over grouped heads of one
size: the causal half's scores a query head, k and v read and dk and dv
written once a **key-value** head (``chipbench/hybrid_flops.py``, key
and value head alike), every attention layer, over the device time of
the events named ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv``."""
from chipbench import flops, hybrid_flops, moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    spent = moe_readers.kernel_seconds(ctx, "flash")
    if not spent:
        return None
    sz = ctx["sz"]
    layers = ctx["family"].attention_layers(sz)
    hd = sz["head_dim"]
    least, bound = flops.roofline_seconds(
        hybrid_flops.flash_flops_per_step(
            sz["per_chip_batch"], sz["heads"], sz["seq"], hd, hd,
            [None] * layers),
        hybrid_flops.flash_bytes_per_step(
            sz["per_chip_batch"], sz["heads"], sz["kv_heads"], sz["seq"],
            hd, hd, layers),
        ctx["peak"].bf16_flops, ctx["peak"].hbm_bytes)
    ctx["notes"].append(f"gqa_flash_roofline: {bound}-bound, least "
                        f"{1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
