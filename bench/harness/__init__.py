"""The chip benchmark's yardstick: cells, corpus generators, the job window,
the plain references, the comparison that decides ``correct``, the
profiler-trace reduction, work counts and the table of peaks.

Nothing here belongs to one configuration, traffic mix or metric: those are
files under ``bench/configs``, ``bench/traffic``, ``bench/checks`` and
``bench/metrics``, found by the names ``BENCHMARK.json`` gives them.
"""
