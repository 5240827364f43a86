"""Chip benchmark of the federation round, the FedBuff flush and serving.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the accelerator it is started on.
"""
