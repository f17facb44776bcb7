"""The decode hot path compiles for a TPU v5e chip.

Nothing runs here: each case lowers with ``interpret=False`` for one chip
of a *described* ``v5e:2x2`` topology and compiles with the TPU compiler
that ships with ``libtpu``, which refuses what the chip would refuse
(block shapes that break the TPU tiling rule, vector loads Mosaic cannot
emit) and which interpret mode never checks.

* ``paged_attention`` alone, at switch-base's widths (12 KV heads, head dim
  64, the MHA layout) and a GQA layout (32 query heads over 8 KV heads,
  head dim 128), each with a plain bf16 pool and an int8 pool with
  per-token scale sidecars, reading one block of a stacked pool.
* The serving engine's four stage programs (decode and prefill chunk, end
  and cloud tier) at switch-base's attention widths: the compiled programs
  copy each page pool leaf once, on entry, and update that copy in place.
  No operation copies, slices or updates one block of a pool leaf, none
  slices or updates a whole one, and no leaf is copied twice.

The topology is described inside a module fixture, never at import time:
only the test process that runs this file loads libtpu.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.hardware import PROFILES
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import attention as attn
from repro.models import kvcache
from repro.models.model import build_model
from repro.serving.stream import EndCloudServingEngine


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
    B, C, ps, pps, R = 8, 1, 16, 64, 3
    P = B * pps

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool_dtype = jnp.int8 if quantized else jnp.bfloat16
    args = (
        sds((B, C, H, hd), jnp.bfloat16),
        sds((R, P + 1, ps, KV * hd), pool_dtype),
        sds((R, P + 1, ps, KV * hd), pool_dtype),
        sds((), jnp.int32),
        sds((B, pps), jnp.int32),
        sds((B, C), jnp.int32),
        sds((B,), jnp.int32),
    )
    scales = {}
    if quantized:
        side = sds((R, P + 1, ps), kvcache.KV_SCALE_DTYPE)
        scales = {"k_scale": side, "v_scale": side}

    def step(q, pool_k, pool_v, layer, table, q_positions, lengths, **kw):
        return paged_attention(
            q, pool_k, pool_v, layer, table, q_positions, lengths,
            interpret=False, **kw,
        )

    compiled = jax.jit(step).lower(*args, **scales).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "paged_attention" in hlo


# ------------------------------------------ the engine's stage programs

STAGES = ("end_step", "cloud_step", "end_prefill_chunk", "cloud_prefill_chunk")
COPIES = ("copy", "copy-start")  # a copy-start's copy-done is the same copy
SLICES = ("dynamic-slice", "dynamic-update-slice")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")


def _computations(hlo: str):
    """Computation name -> its instruction lines; the entry's name is
    ``ENTRY``."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _root_opcode(lines):
    for line in lines:
        m = _INSTR.match(line)
        if m and line.lstrip().startswith("ROOT"):
            return m.group(4)
    return None


@pytest.fixture(scope="module")
def stage_programs(one_chip):
    """The four stage programs of a small engine at switch-base's attention
    widths (12 heads of 64; small FFN, experts and vocabulary), two blocks
    a tier, pools of 1024 pages (a leaf is 50 MB, too large for the
    compiler to stage in VMEM, as a served pool is), compiled for the
    described chip with the paged-attention kernel.  Returns
    ``{stage: (compiled HLO text, pool leaf shapes)}``."""
    base = get_config("switch-base")
    cfg = base.replace(num_layers=8, d_ff=256, vocab_size=512,
                       moe=dataclasses.replace(base.moe, d_ff_expert=128))
    model = build_model(cfg)
    eng = EndCloudServingEngine(
        model, model.init(jax.random.PRNGKey(0)),
        end_profile=PROFILES["a100"], cloud_profile=PROFILES["a100"],
        max_batch=4, max_len=64, force_split=2, kv_pages=1024,
    )
    gsz, C = eng._group_size, eng.prefill_chunk
    eargs = (eng._emask_dev, eng._eres()) if eng._expert_pooled else ()

    def rows(pool, slots, base=0):
        return pool.device_rows([base + s for s in slots],
                                active=np.zeros((len(slots),), bool))

    one = jnp.zeros((1,), jnp.int32)
    lengths = jnp.zeros((gsz,), jnp.int32)
    decode_end = (eng.end_params, jnp.zeros((gsz, 1), jnp.int32),
                  eng._end_pages, rows(eng.end_pool, range(gsz)), lengths,
                  *eargs)
    chunk_end = (eng.end_params, jnp.zeros((1, C), jnp.int32),
                 eng._end_pages, rows(eng.end_pool, [0]), one, one, *eargs)
    z_dec = jax.eval_shape(eng._end_step._fn, *decode_end)[0]
    z_chunk = jax.eval_shape(eng._end_prefill_chunk._fn, *chunk_end)[0]
    calls = {
        "end_step": (eng._end_step, decode_end),
        "cloud_step": (eng._cloud_step, (
            eng.cloud_params, z_dec, eng._cloud_pages,
            rows(eng.cloud_pool, range(gsz), eng._cloud_base), lengths)),
        "end_prefill_chunk": (eng._end_prefill_chunk, chunk_end),
        "cloud_prefill_chunk": (eng._cloud_prefill_chunk, (
            eng.cloud_params, z_chunk, eng._cloud_pages,
            rows(eng.cloud_pool, [0], eng._cloud_base), one, one)),
    }
    shapes = {
        "end": {l.shape for l in jax.tree.leaves(eng._end_pages)},
        "cloud": {l.shape for l in jax.tree.leaves(eng._cloud_pages)},
    }

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    # the kernel, compiled (the process's backend is the CPU): traced anew
    # here, and the traces dropped again after
    impl, resolve = attn._PAGED_ATTN_IMPL, pa_ops.resolve_interpret
    attn.set_paged_attention_impl("kernel")
    pa_ops.resolve_interpret = lambda interpret: False
    jax.clear_caches()
    try:
        out = {
            name: (fn._fn.lower(*on_chip(args)).compile().as_text(),
                   shapes[name.split("_")[0]])
            for name, (fn, args) in calls.items()
        }
    finally:
        pa_ops.resolve_interpret = resolve
        attn.set_paged_attention_impl(impl)
        jax.clear_caches()
    return out


@pytest.mark.parametrize("stage", STAGES)
def test_stage_copies_each_pool_once(stage_programs, stage):
    """The compiled stage moves each pool once: no operation whose result
    is one block of a pool leaf copies, slices or updates it, none whose
    result is a whole leaf slices or updates it (nor fuses either as its
    root), whole-leaf copies number at most the pool's leaves (the entry
    copy of storage that is not donated), and the paged kernel reads the
    stack where it lies."""
    hlo, leaf_shapes = stage_programs[stage]
    block_shapes = set()
    for shape in leaf_shapes:
        block_shapes |= {(1,) + shape[1:], shape[1:]}
    comps = _computations(hlo)
    moved, whole_copies = [], []
    for lines in comps.values():
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            shape = tuple(int(d) for d in m.group(3).split(",") if d)
            whole = shape in leaf_shapes
            if not whole and shape not in block_shapes:
                continue
            op = m.group(4)
            if op == "fusion":
                called = re.search(r"calls=%([\w.-]+)", line).group(1)
                op = _root_opcode(comps[called])
            if op in SLICES or (op in COPIES and not whole):
                moved.append(f"{m.group(1)}: {op} of {list(shape)}")
            elif op in COPIES:
                whole_copies.append(m.group(1))
    assert not moved, moved
    assert sum("paged_attention" in line and "tpu_custom_call" in line
               for line in hlo.splitlines()) >= 1

    n_leaves = 0
    for line in comps["ENTRY"]:
        m = _INSTR.match(line)
        if m and m.group(4) == "parameter":
            shape = tuple(int(d) for d in m.group(3).split(",") if d)
            n_leaves += shape in leaf_shapes
    assert n_leaves == 4, n_leaves  # k and v of two positions
    assert len(whole_copies) <= n_leaves, whole_copies
