"""Chip benchmark: one cell of ``BENCHMARK.json`` per run, driven by data.

Entry point: ``python3 chipbench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.  Everything that belongs to one model
configuration, traffic mix, driver kind or metric lives in a file of its
own under this directory and is found by the name ``BENCHMARK.json``
gives it (see :mod:`chipbench.bench`).
"""
