"""The benchmark: harness, configurations, traffic mixes, metric readers,
trace reducer, plain reference, work functions and the table of peaks.

``python3 benchmarks/suite/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything that
belongs to one configuration, one traffic mix or one per-layer metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it; everything
that belongs to one architecture is ``archs/<model_type>.py``, found by the
configuration's ``model_type``.
"""
