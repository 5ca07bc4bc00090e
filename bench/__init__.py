"""Chip benchmark of repro: cells, traffic, metrics and their yardstick.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the accelerator it
starts on and prints one JSON line. See ``bench/run.py``.
"""
