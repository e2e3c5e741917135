#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``
and ``checks`` (each number compared, beside its limit), with ``--trace
1`` also ``breakdown``.  The numbers compared are also the last lines of
standard error.  Where JAX finds no TPU, or fewer chips than the cell
asks for, it exits non-zero and prints no result.

JAX's compilation cache is ``.jax_cache`` at the root of the checkout,
whatever the environment says, so that only the first run of a cell in a
checkout compiles and two checkouts share nothing.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _checkout() -> None:
    """Import from this checkout, and keep JAX's compilation cache in it
    (read by JAX when it is first imported)."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, benchmark=None, require_tpu: bool = True,
         t_start: float = T_START) -> int:
    args = parse(argv)
    _checkout()
    from bench import cell, check, spec
    cfg = spec.load_cell(args.workload, **(
        {"benchmark": benchmark} if benchmark else {}))
    device = cell.describe_devices()
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cfg.chips):
        print(f"no chip: the cell needs {cfg.chips} TPU chip(s); JAX found "
              f"{device['count']} {device['platform']} device(s)",
              file=sys.stderr)
        return 1
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = cell.run(cfg, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start)
    for line in check.lines(out["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
