#!/usr/bin/env python3
"""Chip smoke: serve qwen2-1.5b at its published widths on a TPU and check
what comes back.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four one-chip replicas behind the router

The default mode drives the main serving path once, through the entry
points a user calls: page-encoded ``Infer`` over a TCP RPC channel ->
page ingest (the Bebop decode kernel) -> ``PagedBatcher`` -> the jitted
``paged_step`` -> the Pallas paged-attention kernels.  The model is
qwen2-1.5b at full width and depth (28 layers, d_model 1536, 12/2 heads,
head_dim 128, vocabulary 151,936, bf16) with random weights from a seed,
served with every ``ServeConfig`` default except ``cache_len`` 1024.

It checks that four concurrent requests (prompts of 7, 33, 130 and 500
tokens) and the same four sent alone answer the same 16 greedy tokens,
that an ``InferStream`` answers 16 tokens, that the scheduler ran mixed
prefill/decode steps with no dense fallback and no worker error, that
the compiled paged step calls a TPU custom kernel, that the Pallas paged
decode and prefill kernels agree with the float32 reference at these
widths, and that the page-decode kernel matches host decoding bit for
bit.  The last line of output is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Where JAX finds no TPU it exits non-zero and prints no result.  Set-up
seconds (model build, compilation) are printed for reading, not as
metrics; the compile cache is the checkout's ``.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2-1.5b"
CACHE_LEN = 1024
PROMPT_LENS = (7, 33, 130, 500)
MAX_NEW = 16
SEED = 0
RPC_TIMEOUT_S = 900.0       # a cold first request includes compilation

# Kernel-vs-reference tolerance (absolute and relative, on outputs of
# unit-normal bf16 inputs).  The kernel and the float32 reference read
# the same bf16 values; the kernel rounds its output to bf16 (2^-9
# relative) and, where the TPU runs a float32 dot as one bf16 pass,
# rounds the softmax weights to bf16 before weighting V (2^-9 of
# sum(p*|v|), which is below 1 for these inputs).  Both stay under 1e-2.
# A block read through the wrong table entry or a misplaced causal mask
# moves outputs by 0.1 or more.
KERNEL_TOL = 1e-2


def _prompts(vocab: int):
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, (1, n), dtype=np.uint32)
            for n in PROMPT_LENS]


def _infer(host: str, port: int, prompt: np.ndarray) -> np.ndarray:
    """One page-encoded Infer on its own connection -> [1, MAX_NEW]."""
    from repro.launch.serve import probe
    return probe(host, port, prompt, MAX_NEW, timeout=RPC_TIMEOUT_S)


def _infer_stream(host: str, port: int, prompt: np.ndarray) -> np.ndarray:
    from repro.core.rpc import Channel, TcpTransport
    from repro.serving.service import (InferenceService, decode_token_page,
                                       encode_prompt_page)
    ch = Channel(TcpTransport.connect(host, port))
    try:
        chunks = [decode_token_page(bytes(bytearray(c["page"])))
                  for c in ch.typed(InferenceService).InferStream(
                      {"page": encode_prompt_page(prompt),
                       "max_new_tokens": MAX_NEW},
                      timeout=RPC_TIMEOUT_S)]
    finally:
        ch.close()
    return np.concatenate(chunks, axis=1)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def _paged_step_hlo(engine, pool) -> str:
    """Compiled HLO of the served paged step at decode shape."""
    import jax
    import jax.numpy as jnp
    b = engine.serve.max_batch

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    i32 = jnp.int32
    return engine.paged_step_fn().lower(
        jax.tree_util.tree_map(spec, engine.params),
        jax.ShapeDtypeStruct((b, 1), i32), jax.tree_util.tree_map(spec, pool),
        jax.ShapeDtypeStruct((b, 1), i32), jax.ShapeDtypeStruct((b, 1), i32),
        jax.ShapeDtypeStruct((b,), i32)).compile().as_text()


def serve_and_check(cfg, log=print) -> dict:
    """Serve ``cfg`` over RPC through the paged path and check the answers.

    Raises on any failed check.  Returns the counts and set-up seconds.
    """
    import jax
    from repro.serving import Engine, ServeConfig, build_server
    from repro.serving.service import InferenceImpl

    t0 = time.perf_counter()
    engine = Engine(cfg, ServeConfig(cache_len=CACHE_LEN), seed=SEED)
    jax.block_until_ready(engine.params)
    impl = InferenceImpl(engine)
    server = build_server(engine, impl=impl)
    host, port, _ = server.listen_tcp()
    t_build = time.perf_counter() - t0
    prompts = _prompts(cfg.vocab_size)
    try:
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(prompts)) as ex:
            together = list(ex.map(lambda p: _infer(host, port, p),
                                   prompts))
        t_first = time.perf_counter() - t0
        streamed = _infer_stream(host, port, prompts[1])
        t0 = time.perf_counter()
        alone = [_infer(host, port, p) for p in prompts]
        t_alone = time.perf_counter() - t0
    finally:
        server.drain(timeout=60.0)
        impl.batcher.close()
    stats = dict(impl.batcher.stats)
    hlo = _paged_step_hlo(engine, impl.batcher.cache.pool)
    custom = "tpu_custom_call" in hlo
    log(f"set-up seconds (not a metric): engine build {t_build:.3f}, "
        f"4 concurrent Infer incl. compilation {t_first:.3f}, "
        f"4 solo Infer {t_alone:.3f}")
    log(f"answers: {len(together)} concurrent + {len(alone)} solo Infer, "
        f"1 InferStream; prompt tokens {list(PROMPT_LENS)}, "
        f"{sum(a.shape[1] for a in together + alone) + streamed.shape[1]} "
        f"tokens generated")
    log("batcher stats: " + json.dumps(stats, sort_keys=True))
    log(f"tpu_custom_call in the compiled paged step: {custom}")
    for n, a, b in zip(PROMPT_LENS, together, alone):
        _check(a.shape == (1, MAX_NEW), f"concurrent answer to the {n}-token "
               f"prompt has shape {a.shape}")
        _check(b.shape == (1, MAX_NEW), f"solo answer to the {n}-token "
               f"prompt has shape {b.shape}")
        _check(np.array_equal(a, b), f"concurrent and solo answers to the "
               f"{n}-token prompt differ: {a.tolist()} vs {b.tolist()}")
    _check(streamed.shape == (1, MAX_NEW),
           f"InferStream answered shape {streamed.shape}")
    _check(stats["mixed_steps"] > 0, "no mixed prefill/decode step ran")
    _check(stats["dense_fallbacks"] == 0, "a request fell back to the "
           "dense engine")
    _check(stats["worker_errors"] == 0, "the batcher worker raised")
    return {"requests": len(together) + len(alone) + 1, "stats": stats,
            "tpu_custom_call": custom, "build_s": t_build,
            "first_s": t_first}


def _paged_inputs(cfg, rng, t: int):
    """Seeded bf16 decode/prefill inputs at ``cfg``'s attention widths,
    with the serving defaults' batch, block size and table width."""
    import jax.numpy as jnp
    b, bs, m = 8, 16, CACHE_LEN // 16
    n = b * m + 1
    shape = (n, cfg.num_kv_heads, bs, cfg.head_dim)
    q = rng.standard_normal((b, cfg.num_heads, t, cfg.head_dim))
    kp, vp = rng.standard_normal(shape), rng.standard_normal(shape)
    tables = np.stack([rng.permutation(np.arange(1, n))[:m]
                       for _ in range(b)]).astype(np.int32)
    # ragged rows: each ends at its own position; a row of a T-wide step
    # is a decode row padded with its repeated position or a prompt chunk
    ends = rng.integers(t, m * bs, b)
    qpos = np.where(np.arange(b)[:, None] % 2 == 0,
                    ends[:, None] - t + 1 + np.arange(t),
                    ends[:, None] + 0 * np.arange(t)).astype(np.int32)
    bf = [jnp.asarray(x, jnp.bfloat16) for x in (q, kp, vp)]
    return (*bf, jnp.asarray(tables), jnp.asarray(qpos))


def check_kernels(cfg, log=print) -> None:
    """Pallas paged kernels vs the float32 reference, and the page-decode
    kernel vs host decoding, at ``cfg``'s widths."""
    import jax
    import jax.numpy as jnp
    from repro.core import pages
    from repro.core import types as T
    from repro.core.device import decode_page_device, plan_device_layout
    from repro.kernels import ops, ref
    from repro.serving.ingest import PageIngest
    from repro.serving.service import (decode_token_page, encode_prompt_page,
                                       prompt_record_struct)

    rng = np.random.default_rng(SEED)
    for t, what in ((1, "decode"), (32, "mixed step"), (5, "verify")):
        q, kp, vp, tables, qpos = _paged_inputs(cfg, rng, t)
        out = ops.paged_attention(q, kp, vp, tables, qpos, impl="pallas")
        with jax.default_matmul_precision("highest"):
            want = ref.paged_attention(*(x.astype(jnp.float32)
                                         for x in (q, kp, vp)),
                                       tables, qpos)
        got = np.asarray(out.astype(jnp.float32))
        want = np.asarray(want)
        err = float(np.max(np.abs(got - want)))
        log(f"paged {what} kernel (T={t}) vs float32 reference: "
            f"max |diff| {err:.6g} (tolerance {KERNEL_TOL} + "
            f"{KERNEL_TOL} x |ref|)")
        _check(np.allclose(got, want, atol=KERNEL_TOL, rtol=KERNEL_TOL),
               f"paged {what} kernel disagrees with the reference "
               f"(max |diff| {err})")

    # the served path: a prompt page through ingest, exact
    toks = rng.integers(0, cfg.vocab_size, (8, max(PROMPT_LENS)),
                        dtype=np.uint32)
    page = encode_prompt_page(toks)
    ing = PageIngest()
    ing.register(prompt_record_struct(toks.shape[1]))
    got = np.asarray(ing.admit(page).columns["tokens"])
    _check(np.array_equal(got, decode_token_page(page).astype(np.int32)),
           "device-decoded prompt page differs from host decoding")
    # every element width and float format the kernel decodes
    s = T.Struct("SmokeRecord", [
        T.Field("id", T.UUID), T.Field("n", T.UINT32),
        T.Field("emb", T.FixedArray(T.BFLOAT16, 64)),
        T.Field("w", T.FixedArray(T.FLOAT32, 8)),
        T.Field("h", T.FixedArray(T.FLOAT16, 6)),
        T.Field("flags", T.FixedArray(T.UINT16, 64)),
        T.Field("tag", T.FixedArray(T.BYTE, 64))])
    layout = plan_device_layout(s)
    raw = rng.integers(0, 256, (64, layout.stride), dtype=np.uint8)
    payload = pages.read_payload(pages.write_page(s.name, raw))
    cols = decode_page_device(jnp.asarray(np.ascontiguousarray(payload)),
                              layout, impl="pallas")
    special = 0
    for c in layout.columns:
        col = raw[:, c.offset:c.offset + c.count * c.elem_size].copy()
        want = {"uint8": lambda: col,
                "uint32": lambda: col.view("<i4"),
                "float32": lambda: col.view("<u4"),
                "bfloat16": lambda: col.view("<u2").astype("<u4") << 16,
                "float16": lambda: col.view("<f2").astype("<f4").view("<u4"),
                "uint16": lambda: col.view("<u2")}[c.wire_dtype]()
        got = np.asarray(cols[c.name])
        if got.dtype == np.float32:         # compare the bits
            got = got.view("<u4")
            exp, man = want & 0x7F800000, want & 0x007FFFFF
            special += int((((exp == 0) | (exp == 0x7F800000))
                            & (man != 0)).sum())
        bad = np.argwhere(got != want)
        _check(not len(bad), f"device-decoded column {c.name} differs from "
               f"host decoding at {len(bad)} of {want.size} elements, e.g. "
               + ", ".join(f"{tuple(i)}: {got[tuple(i)]:#x} vs "
                           f"{want[tuple(i)]:#x}" for i in bad[:4]))
    log("page decode kernel vs host decoding: prompt page "
        f"{toks.shape} and a {len(layout.columns)}-column record page "
        f"({', '.join(sorted({c.wire_dtype for c in layout.columns}))}) "
        f"match bit for bit, {special} NaN and subnormal float patterns "
        "included")


def _device_kind() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _cache_entries(path: str) -> int:
    p = pathlib.Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main_one_chip() -> int:
    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import describe_device

    device = _device_kind()
    if device["platform"] != "tpu":
        print(f"no TPU: JAX found {jax.devices()}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    at_start = _cache_entries(cache)
    print(f"device: {device['kind']} x{device['count']} (serving on "
          f"{describe_device(jax.devices()[0])})")
    print(f"set-up: compile cache {cache}, {at_start} entries at start "
          f"({'warm' if at_start else 'cold'})")
    cfg = get_config(ARCH)
    print(f"model: {cfg.name}, {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
          f"head_dim {cfg.head_dim}, vocab {cfg.vocab_size}, {cfg.dtype}")
    report = serve_and_check(cfg)
    _check(report["tpu_custom_call"], "the compiled paged step has no "
           "tpu_custom_call: the Pallas kernels did not run")
    check_kernels(cfg)
    print(f"set-up: compile cache {cache}, {_cache_entries(cache)} entries "
          f"at end")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": 1}}))
    return 0


# -- four one-chip replicas ----------------------------------------------------


#: the device a launcher process serves on, from its serving line
_DEVICE_RE = re.compile(r"device=(\S+) kind=(.+)$")
#: a line a router launcher forwards from one of its replicas
_REPLICA_RE = re.compile(r"\[(replica \d+)\] (.*)$", re.S)


def _launch(replicas: int, on_line=None):
    """``repro.launch.serve`` serving the smoke's model as a child in its
    own process group; returns once its front door listens."""
    from repro.launch.serve import _spawn_child
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
    argv = [sys.executable, "-m", "repro.launch.serve", "--arch", ARCH,
            "--full", "--cache-len", str(CACHE_LEN),
            "--replicas", str(replicas),
            # route by load alone and never hedge, so each request runs
            # on exactly one replica and four concurrent ones spread out
            "--affinity-prefix", "0", "--no-hedge"]
    return _spawn_child(argv, env, f"{replicas} replica(s)",
                        on_line=on_line, session=True)


def _served_on(label: str, line: str) -> tuple:
    """(label, host, port, device, kind, chip files) of a serving line."""
    from repro.launch.serve import _SERVING_RE, ACCEL_FILE_RE
    m, d = _SERVING_RE.match(line), _DEVICE_RE.search(line)
    _check(bool(m and d), f"{label} printed no device: {line!r}")
    return (label, m.group(1), int(m.group(2)), d.group(1),
            d.group(2).strip(), ACCEL_FILE_RE.findall(d.group(1)))


def _served_requests(host: str, port: int) -> float:
    from repro.core.rpc import Channel, TcpTransport
    from repro.serving.service import InferenceService
    ch = Channel(TcpTransport.connect(host, port))
    try:
        res = ch.typed(InferenceService).Stats({})
    finally:
        ch.close()
    vals = dict(zip(res["names"].split("\n"), res["values"]))
    return float(vals["requests"])


def main_four_chips() -> int:
    """Four one-chip replicas behind the router answer what one process
    answers.  This process never initializes JAX: the launcher's probe and
    its replicas own the chips."""
    from repro.launch.serve import _host_chips
    chips = _host_chips()
    if chips is None or chips < 4:
        print(f"need a host with 4 TPU chips, found {chips}",
              file=sys.stderr)
        return 1
    prompts = _prompts(151936)
    replicas = []

    def on_line(line: str) -> None:
        m = _REPLICA_RE.match(line)
        if m and m.group(2).startswith("bebop-rpc serving"):
            replicas.append(_served_on(m.group(1), m.group(2)))

    four = _launch(4, on_line)     # its replicas have printed by now
    try:
        _check(len(replicas) == 4, f"{len(replicas)} replicas serving")
        # each replica's runtime opened one chip's device file, and no
        # two opened the same one
        files = [r[5] for r in replicas]
        _check(all(len(f) == 1 for f in files) and len(
            {f[0] for f in files}) == 4, f"replicas hold chips {files}, "
            "not one distinct chip each")
        # warm each replica (compilation) directly, then route through
        # the front door once every replica answers quickly
        with ThreadPoolExecutor(len(replicas)) as ex:
            warm = list(ex.map(
                lambda r: [_infer(r[1], r[2], p) for p in prompts],
                replicas))
        before = [_served_requests(r[1], r[2]) for r in replicas]
        with ThreadPoolExecutor(2 * len(prompts)) as ex:
            routed = list(ex.map(lambda p: _infer(four.host, four.port, p),
                                 prompts + prompts))
        after = [_served_requests(r[1], r[2]) for r in replicas]
    finally:
        four.stop_group()
    counts = [(r[0], r[3], int(a - b))
              for r, b, a in zip(replicas, before, after)]
    devices = sorted(r[5][0] for r, b, a in zip(replicas, before, after)
                     if a > b)
    print(f"router: {len(routed)} Infer answered; (replica, device, "
          f"requests served): {counts}")
    _check(len(devices) == 4, f"{len(devices)} distinct chips served the "
           f"routed requests, not 4: {devices}")
    single = []

    def on_own_line(line: str) -> None:
        if line.startswith("bebop-rpc serving"):
            single.append(_served_on("one process", line))

    one = _launch(1, on_own_line)
    try:
        answers = [_infer(one.host, one.port, p) for p in prompts]
    finally:
        one.stop_group()
    for i, (n, want) in enumerate(zip(PROMPT_LENS, answers)):
        _check(want.shape == (1, MAX_NEW), f"one-replica answer shape "
               f"{want.shape}")
        for got in [routed[i], routed[i + len(prompts)]] \
                + [w[i] for w in warm]:
            _check(np.array_equal(got, want), f"{n}-token prompt: four-"
                   f"replica answer {got.tolist()} differs from one-replica "
                   f"answer {want.tolist()}")
    print(f"four replicas on chips {devices} and one process on "
          f"{single[0][3]} gave identical tokens for all {len(prompts)} "
          "prompts")
    print(json.dumps({"ok": True, "device": {
        "platform": replicas[0][3].split(":")[0], "kind": replicas[0][4],
        "count": len(devices)}}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four one-chip replicas behind the "
                         "router, compared with one process")
    args = ap.parse_args(argv)
    return main_four_chips() if args.four_chips else main_one_chip()


if __name__ == "__main__":
    sys.exit(main())
