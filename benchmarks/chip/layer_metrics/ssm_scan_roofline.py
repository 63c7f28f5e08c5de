"""The selective scan's kernels' share of their roofline: the larger of
their HBM traffic over the bandwidth and their vector operations over
the vector unit's peak (``chipbench/ssm_flops.py``: the recurrence has
no matmul form, so the MXU is not its unit), over the device time of
the events named ``ssm_scan_fwd`` and ``ssm_scan_bwd``. The chunk
length (it sets how many entering states the kernels pass) is the
program's gauge ``hvd_ssm_scan_chunks{kind="chunk_length"}``. The note
says which bound it is."""
from chipbench import flops, moe_readers, ssm_flops

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"
CHUNK_LENGTH = 'hvd_ssm_scan_chunks{kind="chunk_length"}'


def read(ctx):
    spent = moe_readers.kernel_seconds(ctx, "scan")
    if not spent:
        return None
    sz, family = ctx["sz"], ctx["family"]
    shape = (sz["per_chip_batch"], sz["seq"], sz["d_inner"], sz["d_state"],
             family.scan_layers(sz))
    chunk = ctx["registry"].get(CHUNK_LENGTH)
    if not chunk:
        return None
    least, bound = flops.roofline_seconds(
        ssm_flops.scan_ops_per_step(*shape),
        ssm_flops.scan_bytes_per_step(*shape, int(chunk)),
        ssm_flops.vector_peak_ops(ctx["peak"].bf16_flops),
        ctx["peak"].hbm_bytes)
    bound = "vector-unit" if bound == "compute" else bound
    ctx["notes"].append(f"ssm_scan_roofline: {bound}-bound, least "
                        f"{1e3 * least:.3f} ms a step")
    return 100.0 * least * ctx["steps"] / spent
