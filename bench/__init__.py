"""Chip benchmark of the split-federated fine-tuning path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on.  Everything
that belongs to one configuration, traffic mix or per-layer metric is a
file of its own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the name that ``BENCHMARK.json`` gives it.
"""
