"""The repo's benchmark: one command, one cell, one JSON line.

``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``. Everything that belongs to one configuration, cell,
traffic mix, kind of run or per-layer metric is a file found by its name
in ``BENCHMARK.json``; see ``run.py`` and ``PERF.md``.
"""
