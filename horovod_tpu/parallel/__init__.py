"""Parallelism beyond the reference's data parallelism.

The reference implements exactly one strategy — synchronous data
parallelism via allreduce (reference: horovod/tensorflow/__init__.py:151
DistributedOptimizer; SURVEY §2.3) — and no sequence/long-context
support at all. These are first-class here:

- ``sharding``        — rule-based parameter sharding (tensor + expert
                        parallelism)
- ``ring_attention``  — sequence/context parallelism for long sequences
- ``pipeline``        — GPipe-style pipeline parallelism over a mesh axis
- ``ssm_scan``        — the selective scan of a state-space layer, as
                        Pallas kernels (forward and backward)
- ``gated_delta``     — the gated delta rule of a Gated DeltaNet layer
                        (a matrix state a head), as Pallas kernels
- ``kda``             — Kimi delta attention: the same rule with a
                        decay for every key channel, as Pallas kernels
- ``qkv_prologue``    — the way from the fused q, k, v projection to
                        both rules' kernels (causal taps, SiLU, the L2
                        norm a head, the scale, the cast), one Pallas
                        pass each way (``qkv_prologue.qkv_prologue``)
- ``delta_epilogue``  — the way from both rules' kernels to the output
                        projection (the RMSNorm a head, its gate, the
                        cast), one Pallas pass each way
                        (``delta_epilogue.delta_epilogue``)
- ``trainer``         — composes dp x tp x sp x ep into one jitted step
"""

from horovod_tpu.parallel.sharding import (
    ShardingRules, fsdp_sharding, infer_sharding, transformer_tp_rules,
)
from horovod_tpu.parallel.ring_attention import (
    ring_attention, make_ring_attention,
)
from horovod_tpu.parallel.ulysses import (
    make_ulysses_attention, ulysses_attention,
)
from horovod_tpu.parallel.pipeline import (
    make_pipeline_apply, pipeline_stages,
)
from horovod_tpu.parallel.trainer import (
    Trainer, TrainerConfig, make_chunked_lm_loss,
)
from horovod_tpu.parallel.ssm_scan import selective_scan
from horovod_tpu.parallel.gated_delta import gated_delta_rule
from horovod_tpu.parallel.kda import kimi_delta_attention


def __getattr__(name):
    # Lazy: pipelined_lm pulls in flax (an optional extra); the rest of
    # this package must stay importable with jax alone.
    if name == "PipelinedLM":
        from horovod_tpu.parallel.pipelined_lm import PipelinedLM
        return PipelinedLM
    raise AttributeError(name)

__all__ = [
    "ShardingRules", "fsdp_sharding", "infer_sharding",
    "transformer_tp_rules",
    "ring_attention", "make_ring_attention",
    "ulysses_attention", "make_ulysses_attention",
    "pipeline_stages", "make_pipeline_apply", "PipelinedLM",
    "Trainer", "TrainerConfig", "make_chunked_lm_loss",
    "selective_scan", "gated_delta_rule", "kimi_delta_attention",
]
