"""Per-layer metric readers, one file per quantity.

``<name>.py`` here serves every metric ``<name>`` or ``<name>.<suffix>``
of ``BENCHMARK.json``: the suffix names the end-to-end metric the reading
moves, not another reading.  Each module has ``read(run)``, which takes a
``harness.Run`` and returns a number, or ``None`` when the run holds
nothing for it to read (the harness then leaves the metric out).
"""
