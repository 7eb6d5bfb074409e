"""On-chip benchmark of this repository, driven by ``BENCHMARK.json``.

One command runs one cell once, from the root of a checkout:

    python3 -m bench --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name, so a cell, a traffic mix or a
per-layer metric is added with files and ``BENCHMARK.json`` entries only:

- ``bench/configs/<config>.json``: the configuration as it is run, with
  its ``source``, the keys ``reduced`` from it and the sizes ``assumed``;
- ``bench/traffic/<workload>.json``: the cell's traffic mix, naming its
  config and the entry (``bench/entries/<entry>.py``) that drives it;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric;
- ``bench/peaks.json``: the chip's published peaks, keyed by
  ``device_kind``.

The plain references that decide ``correct`` live in ``bench/reference``
and import nothing of the program.
"""
