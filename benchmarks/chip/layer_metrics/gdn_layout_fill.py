"""The share of the gated delta rule's state that is the model's: a
key head's width times a value head's as the rule was handed them over
the widths its kernels hold them at (gauge ``hvd_gdn_layout`` of the
call traced last: ``key_dim x value_dim`` over ``laid_key_dim x
laid_value_dim``). 100 where a head is whole lane tiles; key heads of 96
over value heads of 192 laid out to 128 and 256 read 56.25: the kernels'
products run over the laid widths, and ``gdn_roofline`` counts the
recurrence at the published ones."""
from chipbench import moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"

GAUGE = 'hvd_gdn_layout{kind="%s"}'


def read(ctx):
    if not moe_readers.on_the_chip(ctx):
        return None
    dims = [ctx["registry"].get(GAUGE % kind) for kind in
            ("key_dim", "value_dim", "laid_key_dim", "laid_value_dim")]
    if not all(dims):
        return None
    dk, dv, laid_dk, laid_dv = dims
    return 100.0 * dk * dv / (laid_dk * laid_dv)
