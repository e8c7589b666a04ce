"""Chip benchmark of the serving stack: cells named in ``BENCHMARK.json``.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell on the accelerator JAX finds and prints one JSON line.
Everything that belongs to one configuration, traffic mix or metric is a
file of its own under ``configs/``, ``traffic/``, ``limits/`` and
``metrics/``, found by the name ``BENCHMARK.json`` gives it; a model
family is two, ``families/<model_type>.py`` and
``reference/<model_type>.py``, found by the configuration's
``model_type``.
"""
