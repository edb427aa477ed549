"""The benchmark: data-driven cells over the served PackKV engine.

Entry point: ``python bench/run.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>``. See ``spec.py`` for where each part of a cell lives.
"""
