"""A CPU-sized cell, defined only under this directory, and its runs."""
import pathlib
import time

from bench import cell, spec

DATA = pathlib.Path(__file__).resolve().parent / "data"
NAME = "tiny.chat"


def load():
    return spec.load_cell(
        NAME, benchmark=DATA / "benchmark.json",
        traffic_dirs=[DATA / "traffic"],
        metric_dirs=[DATA / "metrics", *spec.METRIC_DIRS])


def run(seed: int, *, traced: bool = False, seconds: float = 2.0) -> dict:
    """One in-process run of the tiny cell, as ``bench/run.py`` makes it
    but without the look for a chip."""
    return cell.run(load(), seed, seconds, traced, t_start=time.monotonic())
