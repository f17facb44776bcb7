"""The decode hot path's Pallas kernel compiles for a TPU v5e chip.

Nothing runs here: each case lowers ``paged_attention`` with
``interpret=False`` for one chip of a *described* ``v5e:2x2`` topology and
compiles it with the TPU compiler that ships with ``libtpu``, which refuses
what the chip would refuse (block shapes that break the TPU tiling rule,
vector loads Mosaic cannot emit) and which interpret mode never checks.
Widths are switch-base's (12 KV heads, head dim 64, the MHA layout) and a
GQA layout (32 query heads over 8 KV heads, head dim 128), each with a plain
bf16 pool and an int8 pool with per-token scale sidecars.

The topology is described inside a module fixture, never at import time:
only the test process that runs this file loads libtpu.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.ops import paged_attention
from repro.models import kvcache


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of any cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize(
    "H,KV,hd", [(12, 12, 64), (32, 8, 128)], ids=["switch-base", "gqa"]
)
def test_paged_attention_compiles_for_v5e(one_chip, H, KV, hd, quantized):
    B, C, ps, pps = 8, 1, 16, 64
    P = B * pps

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool_dtype = jnp.int8 if quantized else jnp.bfloat16
    args = (
        sds((B, C, H, hd), jnp.bfloat16),
        sds((P + 1, ps, KV, hd), pool_dtype),
        sds((P + 1, ps, KV, hd), pool_dtype),
        sds((B, pps), jnp.int32),
        sds((B, C), jnp.int32),
        sds((B,), jnp.int32),
    )
    scales = {}
    if quantized:
        side = sds((P + 1, ps), kvcache.KV_SCALE_DTYPE)
        scales = {"k_scale": side, "v_scale": side}

    def step(q, pool_k, pool_v, table, q_positions, lengths, **kw):
        return paged_attention(
            q, pool_k, pool_v, table, q_positions, lengths,
            interpret=False, **kw,
        )

    compiled = jax.jit(step).lower(*args, **scales).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "paged_attention" in hlo
