"""The flash kernels compile for the chip the benchmark runs on.

The TPU's compiler is installed wherever the tests run and compiles
for a chip that is described, not attached (``v5e:2x2``, device kind
``TPU v5 lite``). Interpret mode on the CPU cannot see what it refuses:
a slice off the tiling, too much VMEM. Each case is one or two seconds;
the whole-step compiles (20 to 40 s) stay out of tier-1.

Such compiles write persistent-cache entries that cannot be read back
without a chip, so the cache is off around this file.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs in /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.parallel.flash_attention import (  # noqa: E402
    _flash_bhsd, _flash_bwd_bhsd, _ladders_for,
)

pytestmark = pytest.mark.fast


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, as a sharding for abstract arguments."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it knows no such chip
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _top(head_dim):
    """The tiles ``flash_attention`` picks for S=2048 at ``head_dim``."""
    q_ladder, k_ladder = _ladders_for(head_dim)
    return q_ladder[0], k_ladder[0]


# (case id, BH, S, D, block_q, block_k)
_CASES = [
    ("bench-d128-512x1024", 64, 2048, 128, 512, 1024),
    ("bench-d128-128x128", 64, 2048, 128, 128, 128),
    ("d64-512x1024", 64, 2048, 64, *_top(64)),
    ("d256-ladder", 32, 2048, 256, *_top(256)),
    # the latent attention of glm47flash-injit-1chip: B4 x 20 heads
    ("glm-d256-s4096", 80, 4096, 256, *_top(256)),
    ("d512-ladder", 16, 2048, 512, *_top(512)),
    ("ring-shard-s512", 64, 512, 128, 512, 512),
]


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("bh,seq,d,block_q,block_k",
                         [c[1:] for c in _CASES],
                         ids=[c[0] for c in _CASES])
def test_flash_forward_compiles_for_v5e(chip, bh, seq, d, block_q,
                                        block_k):
    qkv = jax.ShapeDtypeStruct((bh, seq, d), jnp.bfloat16, sharding=chip)
    offsets = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=chip)
    compiled = _flash_bhsd.lower(
        qkv, qkv, qkv, offsets, causal=True, block_q=block_q,
        block_k=block_k, interpret=False).compile()
    assert _kernel_calls(compiled) == 1


@pytest.mark.parametrize("bh,seq,d,block_q,block_k",
                         [c[1:] for c in _CASES],
                         ids=[c[0] for c in _CASES])
def test_flash_backward_compiles_for_v5e(chip, bh, seq, d, block_q,
                                         block_k):
    qkv = jax.ShapeDtypeStruct((bh, seq, d), jnp.bfloat16, sharding=chip)
    stat = jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32, sharding=chip)
    offsets = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=chip)
    compiled = _flash_bwd_bhsd.lower(
        qkv, qkv, qkv, qkv, stat, stat, offsets, causal=True,
        block_q=block_q, block_k=block_k, interpret=False).compile()
    assert _kernel_calls(compiled) == 2  # dq, and dk/dv


def test_d256_keeps_the_default_pair_and_d512_is_halved():
    """The D=256 cases above compile the pair the chip measured fastest
    of the ladder there (PR 27), the D=512 case the halved one."""
    assert _top(128) == _top(256) == (512, 1024)
    assert _top(512) == (256, 512)
