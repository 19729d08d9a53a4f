"""Chip benchmark of the tree-GGM structure-learning system.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Each configuration (``configs/``), traffic mix (``traffic/``), window
loop (``entries/``), plain reference (``reference/``) and per-layer
metric (``metrics/``) is a file of its own, found by the name that
``BENCHMARK.json`` or the traffic file gives it.
"""
