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
import json
import math
import os
import pathlib
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
ROOT = pathlib.Path(__file__).resolve().parents[1]
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


def _kernels(hlo: str) -> set:
    """The names the compiled program gives its kernel calls, as a device
    trace shows them (the benchmark's kernel metrics match these)."""
    return set(re.findall(r"%([\w-]+?)(?:\.\d+)? = [^\n]*custom-call",
                          hlo))


def _pools(cfg, one_chip):
    """Every layer's K and V pools, and the layer the kernels read."""
    shape = (cfg.num_layers, POOL, cfg.num_kv_heads, BLOCK, cfg.head_dim)
    return (_spec(shape, jnp.bfloat16, one_chip),
            _spec(shape, jnp.bfloat16, one_chip))


def _layer(one_chip):
    return _spec((), jnp.int32, one_chip)


def test_paged_decode_kernel_compiles(cfg, one_chip):
    q = _spec((BATCH, cfg.num_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    tables = _spec((BATCH, TABLE), jnp.int32, one_chip)
    ctx = _spec((BATCH,), jnp.int32, one_chip)
    hlo = _hlo(functools.partial(paged_attention, interpret=False),
               q, *_pools(cfg, one_chip), tables, ctx, _layer(one_chip))
    assert "tpu_custom_call" in hlo
    assert _kernels(hlo) == {"paged_attention"}


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
               q, *_pools(cfg, one_chip), tables, qpos, _layer(one_chip))
    assert "tpu_custom_call" in hlo
    assert _kernels(hlo) == {"paged_prefill_attention"}


@pytest.mark.parametrize("rows,seq", [(1, 7), (8, 500)])
def test_prompt_page_decode_compiles(one_chip, rows, seq):
    layout = plan_device_layout(prompt_record_struct(seq))
    fields = tuple(c.as_field("int32") for c in layout.columns)
    words = _spec((rows, layout.stride // 4), jnp.uint32, one_chip)
    hlo = _hlo(functools.partial(decode_columns, fields=fields,
                                 interpret=False), words)
    assert "tpu_custom_call" in hlo
    assert _kernels(hlo) == {"bebop_decode"}


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
    assert _kernels(hlo) == {"bebop_decode", "bebop_decode_elements"}
    f32_ops = {op for dt, op in _INSTR.findall(hlo) if dt == "f32"}
    assert "custom-call" in f32_ops
    assert f32_ops <= {"custom-call", "get-tuple-element", "bitcast",
                       "copy", "tuple"}, f32_ops


#: an HLO computation's first line, and one instruction of it
_COMP = re.compile(r"^(?:ENTRY )?%([\w.-]+) .*\{\s*$")
_ARRAY = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w-]+)\(")
#: opcodes that name a buffer without moving it
_NO_MOVE = {"parameter", "get-tuple-element", "bitcast", "tuple"}


def _computations(hlo: str) -> dict:
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _updates_in_place(body) -> bool:
    """A fusion that writes into its first operand: its root is a scatter
    or dynamic-update-slice of a fusion parameter, and it copies nothing."""
    ops_ = [m.group(3) for m in map(_ARRAY.match, body) if m]
    root = next(line for line in body if line.lstrip().startswith("ROOT"))
    m = _ARRAY.match(root)
    first = re.search(r"\(%([\w.-]+)", root.split(" = ", 1)[1]).group(1)
    return (m is not None and m.group(3) in ("scatter", "dynamic-update-slice")
            and first.startswith("param") and "copy" not in ops_)


def _pool_movers(hlo: str, sizes) -> list:
    """Instructions outside fusions that make an array of one of ``sizes``
    elements, other than names of a buffer and in-place update fusions."""
    comps = _computations(hlo)
    fused = set(re.findall(r"calls=%([\w.-]+)", hlo))
    out = []
    for name, body in comps.items():
        if name in fused:
            continue
        for line in body:
            m = _ARRAY.match(line)
            if not m or m.group(3) in _NO_MOVE:
                continue
            n = math.prod(int(d) for d in m.group(2).split(",") if d)
            if n not in sizes:
                continue
            if m.group(3) == "fusion" and _updates_in_place(
                    comps[re.search(r"calls=%([\w.-]+)", line).group(1)]):
                continue
            out.append(f"{m.group(1)} {m.group(3)}[{m.group(2)}]")
    return out


@pytest.mark.parametrize("chunk", [
    1,      # decode step
    32,     # mixed prefill/decode step at the default prefill_chunk
])
def test_paged_step_memory(cfg, one_chip, monkeypatch, chunk):
    """The whole 28-layer paged step at the serving defaults compiles for
    the chip with the Pallas kernels and updates the donated pool in
    place: the pool is aliased to the new one, the temporaries are
    smaller than one pool, and no instruction copies, slices or retiles
    the pool or one layer of it.  Prints the compiler's memory analysis
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
        params, i32((BATCH, chunk)), pool, i32((BATCH, TABLE)),
        i32((BATCH, chunk)), i32((BATCH,))).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(pool))
    print(f"paged step, C={chunk}, pool of {POOL} blocks ({pool_bytes} B): "
          f"arguments {mem.argument_size_in_bytes} B, aliased "
          f"{mem.alias_size_in_bytes} B, temporaries "
          f"{mem.temp_size_in_bytes} B, output {mem.output_size_in_bytes} B")
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes
    k = pool["k"]
    assert _pool_movers(hlo, {k.size, k.size // k.shape[0]}) == []


# Digests of the paged kernels' lowering at qwen2-1.5b's chat-cell shapes
# (16 rows, 12/2 heads, 28-layer pool of 4,097 blocks, 256-block tables),
# taken from the tree before windows were added: the kernel bodies with
# their source locations stripped, and the program around them.  With
# window 0 the kernels must lower to exactly this.
WINDOW0_DIGESTS = {
    "decode":
        "5841ad1618af8311873c7de8e1f08ece765d2f623d6ede21d6d1d0bff3c30524",
    "prefill":
        "8c99c3ee0eb43cb6931d8dea02ac6617884c9a2fd2be9ac1d383e0e515e834c8",
}
_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def _canonical(lowered_text: str) -> str:
    """A lowering's text with each Mosaic kernel body decoded and printed
    without source locations (which move with every edit of the file)."""
    import base64
    import hashlib
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(b64):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            return ir.Module.parse(base64.b64decode(b64)).operation.get_asm(
                enable_debug_info=False)
    outer = re.sub(r"loc\([^)]*\)", "", _BODY.sub("BODY", lowered_text))
    kernels = "".join(body(b) for b in _BODY.findall(lowered_text))
    return hashlib.sha256((outer + kernels).encode()).hexdigest()


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_window0_kernels_lower_as_before(one_chip, kind):
    b, hq, hkv, hd, m, n, layers = 16, 12, 2, 128, 256, 4097, 28
    pool = _spec((layers, n, hkv, BLOCK, hd), jnp.bfloat16, one_chip)
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    if kind == "decode":
        args = (_spec((b, hq, hd), jnp.bfloat16, one_chip), pool, pool,
                i32((b, m)), i32((b,)), i32(()))
        fn = (lambda q, k, v, t, c, l:
              paged_attention(q, k, v, t, c, l, window=0))
    else:
        args = (_spec((b, hq, 32, hd), jnp.bfloat16, one_chip), pool, pool,
                i32((b, m)), i32((b, 32)), i32(()))
        fn = (lambda q, k, v, t, c, l:
              paged_prefill_attention(q, k, v, t, c, l, window=0))
    assert _canonical(jax.jit(fn).lower(*args).as_text()) \
        == WINDOW0_DIGESTS[kind]


@pytest.mark.parametrize("chunk", [1, 32])
def test_mellum_paged_step_memory(one_chip, monkeypatch, chunk):
    """Mellum2's 28-layer paged step at the mellum2-e16.code cell's sizes
    (16 experts of 64 held, 16 rows, tables of ``cache_len`` / 16 = 528
    blocks, 8,449 blocks for the 7 full layers, 16 rings of 10 blocks of
    128 tokens for the 21 window layers; bench/configs/mellum2-e16.json)
    compiles for the chip with the Pallas kernels, window and full, and
    updates both donated pools in place.  Prints the compiler's memory
    analysis (``pytest -s``); nothing here is measured on a device."""
    import dataclasses
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    base = get_config("mellum2-12b-a2.5b")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe,
                                                            held=16))
    from repro.serving.kv_cache import ring_geometry
    serve = json.loads((ROOT / "bench/configs/mellum2-e16.json")
                       .read_text())["serve"]
    rows, table, blocks = (serve["max_batch"], serve["cache_len"] // BLOCK,
                           serve["num_blocks"])
    assert (rows, table, blocks) == (16, 528, 8449)
    ring_bs, ring = ring_geometry(cfg.window, BLOCK, 32)
    assert (ring_bs, ring) == (128, 10)
    model = get_model(cfg)
    specs = functools.partial(jax.tree_util.tree_map,
                              lambda x: _spec(x.shape, x.dtype, one_chip))
    params = specs(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = specs(jax.eval_shape(
        lambda: model.init_paged_pool(blocks, BLOCK, 1 + rows * ring,
                                      ring_bs)))
    assert pool["k"].shape[0] == 7 and pool["ring_k"].shape[0] == 21
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.paged_step, donate_argnums=(2,)).lower(
        params, i32((rows, chunk)), pool, i32((rows, table)),
        i32((rows, chunk)), i32((rows,))).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(pool))
    print(f"mellum2 paged step, C={chunk}: pool and rings {pool_bytes} B, "
          f"arguments {mem.argument_size_in_bytes} B, aliased "
          f"{mem.alias_size_in_bytes} B, temporaries "
          f"{mem.temp_size_in_bytes} B, output {mem.output_size_in_bytes} B")
    hlo = compiled.as_text()
    assert {"paged_attention" if chunk == 1 else "paged_prefill_attention",
            "ragged-dot-none"} <= _kernels(hlo)
    assert mem.alias_size_in_bytes == pool_bytes
    # no copy of either pool: the largest temporary is a relaid copy of
    # the query weights' stack (28 x 2,304 x 4,096 in bfloat16, 528 MB)
    assert mem.temp_size_in_bytes < pool["k"].size * 2
    k, ring_k = pool["k"], pool["ring_k"]
    assert _pool_movers(hlo, {k.size, k.size // k.shape[0], ring_k.size,
                              ring_k.size // ring_k.shape[0]}) == []
