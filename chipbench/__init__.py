"""Chip benchmark of the serving path: one cell, one run, one result line.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``; see ``chipbench/README.md``.
"""
