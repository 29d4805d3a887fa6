"""The chip benchmark of the integral-histogram analytics service.

``BENCHMARK.json`` at the checkout root names the cells; ``run.py`` runs
one.  Configurations are ``configs/<name>.json``, traffic mixes
``traffic/<name>.json`` (read by the one generator, ``scene.py``), and
per-layer metric readers ``metrics/<name>.py``.  The yardstick lives here
too: the plain NumPy reference (``reference.py``) and the comparison
that decides ``correct`` (``check.py``), the trace reduction
(``trace.py``), the peaks table (``peaks.py``) and the kernels' byte
counts (``work.py``).
"""
