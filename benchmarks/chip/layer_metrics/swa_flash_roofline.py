"""The flash kernels' share of their roofline where layers differ in
their window: the scores each kept layer's mask allows a query head (a
band of ``sliding_window_size`` keys, or the causal half), k and v read
and dk and dv written once a **key-value** head
(``chipbench/smallthinker_flops.py``), over the device time of the
events named ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``."""
from chipbench import flops, moe_readers, smallthinker_flops

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    spent = moe_readers.kernel_seconds(ctx, "flash")
    if not spent:
        return None
    sz = ctx["sz"]
    least, bound = flops.roofline_seconds(
        smallthinker_flops.flash_flops_per_step(sz),
        smallthinker_flops.flash_bytes_per_step(sz),
        ctx["peak"].bf16_flops, ctx["peak"].hbm_bytes)
    ctx["notes"].append(f"swa_flash_roofline: {bound}-bound, least "
                        f"{1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
