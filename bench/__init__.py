"""The chip benchmark of the spectral-clustering system.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it is started on and
prints one JSON result line.  Everything a cell needs is found by name:
``configs/<config>.json`` (the deployment), ``mixes/<traffic>.json`` (the
traffic), ``layers/<metric>.py`` (one reader per per-layer metric),
``work/<kernel>.py`` (a kernel's operations and bytes) and ``peaks.json``.
"""
