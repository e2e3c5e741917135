"""Compile rehearsals: the main path's Pallas kernels, built for a TPU v5e
at qwen2-1.5b's published widths, without a chip.

The TPU compiler is installed even where no TPU is attached; compiling
for a *described* ``v5e:2x2`` topology raises exactly what the chip's
compiler would raise (tiling, VMEM, bit-width rules that interpret mode
never checks).  Nothing runs, so these say nothing about results or time.

The topology is described only inside the module fixture: describing it
loads the TPU library, which one process at a time may hold, so it must
never happen while a test module is imported or collected.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import types as T
from repro.kernels import ops
from repro.kernels.bebop_decode import decode_columns
from repro.models import get_model
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention)
from repro.serving.service import prompt_record_struct
from repro.core.device import plan_device_layout

BATCH = 8            # ServeConfig.max_batch default
BLOCK = 16           # ServeConfig.block_size default
TABLE = 64           # cache_len 1024 / block 16
POOL = 513           # auto-sized pool: max_batch * TABLE + null block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen2-1.5b")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _pools(cfg, one_chip):
    shape = (POOL, cfg.num_kv_heads, BLOCK, cfg.head_dim)
    return (_spec(shape, jnp.bfloat16, one_chip),
            _spec(shape, jnp.bfloat16, one_chip))


def test_paged_decode_kernel_compiles(cfg, one_chip):
    q = _spec((BATCH, cfg.num_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    tables = _spec((BATCH, TABLE), jnp.int32, one_chip)
    ctx = _spec((BATCH,), jnp.int32, one_chip)
    hlo = _hlo(functools.partial(paged_attention, interpret=False),
               q, *_pools(cfg, one_chip), tables, ctx)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("t", [
    32,     # mixed prefill/decode step at the default prefill_chunk
    5,      # speculative verify at the default spec_len 4
])
def test_paged_prefill_kernel_compiles(cfg, one_chip, t):
    q = _spec((BATCH, cfg.num_heads, t, cfg.head_dim), jnp.bfloat16,
              one_chip)
    tables = _spec((BATCH, TABLE), jnp.int32, one_chip)
    qpos = _spec((BATCH, t), jnp.int32, one_chip)
    hlo = _hlo(functools.partial(paged_prefill_attention, interpret=False),
               q, *_pools(cfg, one_chip), tables, qpos)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows,seq", [(1, 7), (8, 500)])
def test_prompt_page_decode_compiles(one_chip, rows, seq):
    layout = plan_device_layout(prompt_record_struct(seq))
    fields = tuple(c.as_field("int32") for c in layout.columns)
    words = _spec((rows, layout.stride // 4), jnp.uint32, one_chip)
    hlo = _hlo(functools.partial(decode_columns, fields=fields,
                                 interpret=False), words)
    assert "tpu_custom_call" in hlo


#: an HLO instruction's result type and opcode
_INSTR = re.compile(r"= (\w+)\[[\d,]*\]\S* ([\w-]+)\(")


def test_float_columns_stored_by_kernels(one_chip):
    """Every float32 value of a float-column decode is written by a kernel
    and at most moved by a layout copy, never made by an XLA fusion,
    which on a TPU may flush subnormals and replace NaN payloads."""
    s = T.Struct("Floats", [
        T.Field("id", T.UUID), T.Field("n", T.UINT32),
        T.Field("emb", T.FixedArray(T.BFLOAT16, 64)),
        T.Field("w", T.FixedArray(T.FLOAT32, 8)),
        T.Field("h", T.FixedArray(T.FLOAT16, 6)),
        T.Field("flags", T.FixedArray(T.UINT16, 6))])
    layout = plan_device_layout(s)
    fields = tuple(c.as_field("float32" if "float" in c.wire_dtype
                              else "int32") for c in layout.columns)
    page = _spec((64, layout.stride), jnp.uint8, one_chip)
    hlo = _hlo(functools.partial(decode_columns, fields=fields,
                                 interpret=False), page)
    f32_ops = {op for dt, op in _INSTR.findall(hlo) if dt == "f32"}
    assert "custom-call" in f32_ops
    assert f32_ops <= {"custom-call", "get-tuple-element", "bitcast",
                       "copy", "tuple"}, f32_ops


def test_paged_step_memory(cfg, one_chip, monkeypatch):
    """The whole 28-layer paged decode step at the serving defaults
    compiles for the chip with the Pallas kernels, and the donated pool
    is aliased to the new one.  Prints the compiler's memory analysis
    (``pytest -s``); nothing here is measured on a device."""
    # the kernels are picked by the default backend, which here is the CPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    model = get_model(cfg)
    specs = functools.partial(jax.tree_util.tree_map,
                              lambda x: _spec(x.shape, x.dtype, one_chip))
    params = specs(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = specs(jax.eval_shape(lambda: model.init_paged_pool(POOL, BLOCK)))
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.paged_step, donate_argnums=(2,)).lower(
        params, i32((BATCH, 1)), pool, i32((BATCH, TABLE)), i32((BATCH, 1)),
        i32((BATCH,))).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(pool))
    print(f"paged step, pool of {POOL} blocks ({pool_bytes} B): arguments "
          f"{mem.argument_size_in_bytes} B, aliased "
          f"{mem.alias_size_in_bytes} B, temporaries "
          f"{mem.temp_size_in_bytes} B, output {mem.output_size_in_bytes} B")
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.alias_size_in_bytes == pool_bytes
