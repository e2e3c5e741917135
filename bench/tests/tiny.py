"""CPU-sized cells, defined only under this directory, and their runs."""
import json
import pathlib
import time

from bench import cell, spec

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELLS = [w["name"] for w in json.loads(
    (DATA / "benchmark.json").read_text())["workloads"]]
NAME = CELLS[0]         # the cell of the served configurations' family
COUNTED = CELLS[1]      # the same sizes through the test family


def load(name: str = NAME):
    return spec.load_cell(
        name, benchmark=DATA / "benchmark.json",
        traffic_dirs=[DATA / "traffic"],
        metric_dirs=[DATA / "metrics", *spec.METRIC_DIRS],
        family_dirs=[DATA / "families", *spec.FAMILY_DIRS])


def run(seed: int, *, traced: bool = False, seconds: float = 2.0,
        name: str = NAME) -> dict:
    """One in-process run of a tiny cell, as ``bench/run.py`` makes it
    but without the look for a chip."""
    return cell.run(load(name), seed, seconds, traced,
                    t_start=time.monotonic())
