"""The serving benchmark: harness, yardstick and per-cell data.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the chip.  Everything specific
to a configuration, a traffic mix or a metric lives in files of its own
under ``bench/configs``, ``bench/traffic``, ``bench/metrics`` and
``bench/limits``, found by the names in ``BENCHMARK.json``; everything
specific to an architecture (sizes, weight tree, plain reference, counts)
in the model module ``bench/models/<m>.py`` that its configuration file
names.
"""
