"""The served LSH index's benchmark: one cell per run, driven by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` on the chip. What belongs to a
configuration, a traffic mix or a per-layer metric lives in files of its
own under this directory, found by the names in ``BENCHMARK.json``.
"""
