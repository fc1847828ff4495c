"""On-chip serving benchmark (``python3 bench/run.py --workload <name>``).

Every cell of ``BENCHMARK.json`` is found by name in files of its own:
a configuration under ``configs/``, its architecture module under
``arch/`` (published keys, reference weights and forward, work counts),
a traffic mix under ``traffic/``, a per-layer metric reader under
``metrics/``, a layer's work count under ``work/`` and the chip's peaks
in ``peaks.json``.
"""
