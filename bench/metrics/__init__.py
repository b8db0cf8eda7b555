"""Metric readers: ``<metric>.py`` defines ``read(run) -> float | None``.

``run`` carries ``driver`` (the driver's kind), ``records`` (what the
driver recorded), ``spans`` (the harness's host spans), ``setup_s``,
``seconds``, ``memory_peak_bytes``, ``trace`` (the reduced profiler trace
in a ``--trace 1`` run, else None), ``peaks`` (this device's row of
``bench/peaks.json``) and ``params`` (the workload file).  A reader that
finds nothing to read returns None, and the metric is left out.
"""
