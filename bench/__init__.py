"""Chip benchmark of the ParisKV serving engine, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix, cell or metric is a file of
its own under ``bench/``, found by the name ``BENCHMARK.json`` gives it.
"""
