"""Chip benchmark of the serving stack: one command runs one cell once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the benchmark reads lives here and is found by name from
BENCHMARK.json: a configuration is ``configs/<config>.json``, a traffic
mix ``traffic/<mix>.json``, a per-layer metric ``metrics/<metric>.py``,
and a configuration's ``model_type`` names its family,
``families/<model_type>.py``.
"""
