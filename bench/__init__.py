"""Chip benchmark for the DisPFL training rounds.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once.  Everything a cell needs is found by name: its
configuration (``configs/<config>.json`` and the plain reference beside it,
``configs/<config>.py``), its traffic mix (``traffic/<traffic>.json``), its
cell record (``workloads/<cell>.json``) and each per-layer metric's reader
(``metrics/<metric>.py``).
"""
