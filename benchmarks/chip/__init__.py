"""Chip benchmark: one cell (configuration x traffic mix) per run.

Run from the root of a checkout:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Every piece is found by name from ``BENCHMARK.json``: a configuration is
``configs/<name>.json``, a traffic mix ``traffic/<name>.json`` (read by the
one generator in ``traffic.py``), a per-layer metric
``layer_metrics/<name>.py``, and a model family's plain reference and its
adapter to the program ``references/<model_type>.py`` and
``adapters/<model_type>.py``.
"""
