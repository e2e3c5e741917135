"""The on-chip serving benchmark.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the chip it is started on and
prints one JSON line.  Everything that belongs to one configuration, one
traffic mix or one per-layer metric lives in a file of its own, found by
the name ``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the model's sizes, its serving sizes,
  its source and the limits of the comparison that decides ``correct``;
- ``bench/traffic/<traffic>.json``: the parameters of one traffic mix,
  read by the one generator in ``traffic.py``;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

The yardstick lives here too: the seeded weights (``weights.py``), the
plain float32 reference (``reference.py``), the comparison
(``check.py``), the operation and byte counts (``flops.py``), the peaks
(``peaks.json``) and the reduction of a profiler trace (``trace.py``).
"""
