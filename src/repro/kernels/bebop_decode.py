"""On-device Bebop page decode — the paper's core insight, TPU-native.

The paper's CPU decoder is "a single load instruction" because every wire
type is fixed-width.  On TPU the same property means something stronger: a
page of N fixed-layout records is a dense ``[N, stride]`` byte matrix whose
column layout is known at schema-compile time, so *deserialization is a
layout transformation* — slice columns, shift, mask, bitcast — with zero
data-dependent control flow.  Varint data cannot be decoded this way at all
(the byte width of element k depends on the *values* of elements 0..k-1,
a serial dependency); fixed-width data decodes as pure vector loads.

This kernel implements column extraction:

    words  : [N, stride / 4] uint32 in HBM (a page's rows, read four
             little-endian bytes at a time; core/pages.py writes them)
    output : [N, count]  of the field's dtype

tiled ``block_n`` records at a time through VMEM.  The TPU compiler does
not change bit widths inside a kernel, so the kernel works on 32-bit
words only: a 4-byte field is its words, and a 2- or 1-byte field is
extracted by shifting and masking each word into one *plane* per byte
position (plane p holds the elements at ``word * per + p``).  XLA
interleaves the planes back into element order, and a second,
elementwise kernel turns each element into its output value: a bfloat16
is the high half of the float32 with the same value (§3.2's wire
definition), and a float16 is widened by integer arithmetic, which
keeps NaN payloads.

On a TPU, XLA may move 32-bit words between layouts as float32 values:
a word that reads as a subnormal float32 comes out zero and one that
reads as a NaN comes out as the canonical NaN.  (The interleave of a
64-wide bfloat16 column did this on a v5e; a kernel's loads and stores
keep every bit.)  So each element a plane carries is *tagged*: its bits
sit in the low mantissa under the exponent of 1.0, which makes every
word XLA moves between the kernels a normal float32, and the second
kernel strips the tag.  Every column then decodes to exactly the host's
value (numpy's, for a float16 NaN; XLA's float16 convert in
``ref.DECODERS`` quiets it).  A bfloat16 or float16 *output* dtype is an
XLA convert of the float32: read back from a TPU, arrays of those
dtypes held canonical NaNs, and bfloat16 ones zero for subnormals, even
when made by a bare bitcast.

The paper's "GPU-side deserialization for direct device memory placement"
future-work item is exactly this: the host DMAs raw page bytes to HBM and
the accelerator materializes tensors in the layout the model consumes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# wire dtype -> bytes per element
_SIZE = {"uint32": 4, "int32": 4, "float32": 4, "uint16": 2, "bfloat16": 2,
         "float16": 2, "uint8": 1, "byte": 1, "bool": 1}
#: the float32 1.0's exponent: a plane word is this over its element's bits
_TAG = 0x3F800000


def page_words(pages: jax.Array) -> jax.Array:
    """[N, stride] u8 page rows -> [N, stride / 4] u32 little-endian words.

    Runs as an XLA op before the kernel; the serving path skips it by
    staging the page as words on the host (serving/ingest.py).
    """
    if pages.dtype == jnp.uint32:
        return pages
    n, stride = pages.shape
    if stride % 4:
        raise ValueError(f"page stride {stride} is not a multiple of 4 bytes")
    return jax.lax.bitcast_convert_type(pages.reshape(n, stride // 4, 4),
                                        jnp.uint32)


def _plan(offset: int, count: int, wire_dtype: str, out_dtype):
    """Static word window and plane dtype of one field."""
    if wire_dtype not in _SIZE:
        raise ValueError(f"unsupported wire dtype {wire_dtype}")
    size = _SIZE[wire_dtype]
    if offset % size:
        raise ValueError(f"{wire_dtype} column at byte {offset} is not "
                         f"aligned to its {size}-byte elements")
    w0 = offset // 4
    nw = -(-(offset + size * count) // 4) - w0
    # a 4-byte field is emitted in its output dtype where that is 32-bit
    # (else in its own, converted after the kernel); narrower fields leave
    # tagged u32 planes for the interleave
    if size < 4:
        kdt = jnp.uint32
    elif jnp.dtype(out_dtype).itemsize == 4:
        kdt = out_dtype
    else:
        kdt = wire_dtype
    return size, w0, nw, kdt


def _planes(words, wire_dtype: str, size: int, kdt):
    """u32 words [bn, nw] -> one plane per byte position, each [bn, nw]."""
    if size == 4:
        x = jax.lax.bitcast_convert_type(words, wire_dtype)
        if jnp.dtype(kdt) == x.dtype:
            return [x]
        if jnp.issubdtype(x.dtype, jnp.integer) \
                and jnp.issubdtype(kdt, jnp.integer):  # two's complement
            return [jax.lax.bitcast_convert_type(x, kdt)]
        return [x.astype(kdt)]
    bits = 8 * size
    mask = (1 << bits) - 1
    return [(words >> (bits * p)) & mask | _TAG for p in range(4 // size)]


def _element_kernel(x_ref, o_ref, *, wire_dtype):
    """Tagged u32 elements -> their values: float32 bits for a 2-byte
    float, the integer otherwise."""
    v = x_ref[...] & 0xFFFF
    if wire_dtype == "bfloat16":
        v = v << 16
    elif wire_dtype == "float16":
        v = _f16_to_f32_bits(v)
    o_ref[...] = jax.lax.bitcast_convert_type(v, o_ref.dtype)


def _elements(tagged, wire_dtype: str, block_n: int, interpret: bool):
    """[N, count] tagged u32 -> [N, count] float32 or int32, by a kernel."""
    n, count = tagged.shape
    dt = jnp.float32 if wire_dtype in ("bfloat16", "float16") else jnp.int32
    spec = pl.BlockSpec((block_n, count), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_element_kernel, wire_dtype=wire_dtype),
        out_shape=jax.ShapeDtypeStruct(tagged.shape, dt),
        in_specs=[spec], out_specs=spec, grid=(n // block_n,),
        interpret=interpret)(tagged)


def _f16_to_f32_bits(h):
    """u32 holding binary16 bits -> the bits of the same binary32 value."""
    sign = (h & 0x8000) << 16
    exp = (h >> 10) & 0x1F
    man = h & 0x3FF
    normal = ((exp + 112) << 23) | (man << 13)
    # a subnormal is man * 2^-24, a normal float32: exact in float math
    sub = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(man, jnp.int32).astype(jnp.float32)
        * 2.0 ** -24, jnp.uint32)
    special = 0x7F800000 | (man << 13)      # infinity, NaN and its payload
    return sign | jnp.where(exp == 0x1F, special,
                            jnp.where(exp == 0, sub, normal))


def _decode_kernel(x_ref, *o_refs, specs):
    i = 0
    for wire_dtype, size, w0, nw, kdt in specs:
        planes = _planes(x_ref[:, w0:w0 + nw], wire_dtype, size, kdt)
        for p in range(4 // size):
            o_refs[i + p][...] = planes[p]
        i += 4 // size


def _assemble(planes, offset: int, count: int, wire_dtype: str, out_dtype,
              block_n: int, interpret: bool):
    """Planes -> [N, count] in element order and the output dtype."""
    if len(planes) == 1:
        x = planes[0]
    else:
        n, nw = planes[0].shape
        lead = offset % 4 // (4 // len(planes))
        x = jnp.stack(planes, -1).reshape(n, nw * len(planes))
        x = _elements(x[:, lead:lead + count], wire_dtype, block_n,
                      interpret)
    return x if x.dtype == jnp.dtype(out_dtype) else x.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("fields", "block_n", "interpret"))
def decode_columns(pages: jax.Array, *, fields: tuple,
                   block_n: int = 256, interpret: bool = False):
    """Decode several columns in ONE pass over the page bytes.

    ``pages``: [N, stride / 4] u32 words, or [N, stride] u8 rows with a
    stride that is a multiple of 4 (see :func:`page_words`).  N must be
    a multiple of ``block_n`` (pages are written with power-of-two record
    counts; callers pad short tails).  ``fields``: tuple of (byte offset,
    count, wire_dtype, out_dtype_name).  Reading the page block once and
    emitting every column amortizes the HBM->VMEM transfer across fields —
    the kernel-fusion analogue of the paper's single-pass decoder.
    """
    words = page_words(pages)
    n, width = words.shape
    block_n = min(block_n, n)
    if n % block_n:
        raise ValueError(f"record count {n} not divisible by block {block_n}")
    specs, out_shapes = [], []
    for off, cnt, wd, od in fields:
        size, w0, nw, kdt = _plan(off, cnt, wd, jnp.dtype(od))
        specs.append((wd, size, w0, nw, kdt))
        out_shapes += [jax.ShapeDtypeStruct((n, nw), kdt)] * (4 // size)
    planes = pl.pallas_call(
        functools.partial(_decode_kernel, specs=tuple(specs)),
        out_shape=out_shapes,
        in_specs=[pl.BlockSpec((block_n, width), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_n, s.shape[1]), lambda i: (i, 0))
                   for s in out_shapes],
        grid=(n // block_n,),
        interpret=interpret,
    )(words)
    outs, i = [], 0
    for (off, cnt, wd, od), (_, size, _, _, _) in zip(fields, specs):
        k = 4 // size
        outs.append(_assemble(planes[i:i + k], off, cnt, wd, jnp.dtype(od),
                              block_n, interpret))
        i += k
    return outs


def decode_column(pages: jax.Array, *, offset: int, count: int,
                  wire_dtype: str, out_dtype=None,
                  block_n: int = 256, interpret: bool = False) -> jax.Array:
    """Extract one fixed-width column from a page of records."""
    od = jnp.dtype(out_dtype or _default_out(wire_dtype)).name
    return decode_columns(pages, fields=((offset, count, wire_dtype, od),),
                          block_n=block_n, interpret=interpret)[0]


def _default_out(wire_dtype: str):
    return {
        "uint32": jnp.uint32, "int32": jnp.int32, "float32": jnp.float32,
        "uint16": jnp.uint16, "bfloat16": jnp.float32,
        "float16": jnp.float32, "uint8": jnp.uint8, "byte": jnp.uint8,
        "bool": jnp.uint8,
    }[wire_dtype]
