"""Device time a step under the attention scopes (``attn``, ``mla``,
``diff_attn`` and its ``.window`` and ``.cross``, ``gated_attn``,
``normed_attn``) outside the flash kernels (the events named
``flash_*``, which the ``*flash_roofline`` metrics read): projections,
norms, rotary, concatenations, the ``[B,S,H,D]`` transposes."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(
        ctx, ("attn", "mla", "diff_attn", "diff_attn.", "gated_attn",
              "normed_attn"), less="flash_")
