"""Per-layer metric readers: ``<metric>.py`` for each per-layer metric of
``BENCHMARK.json``, found by its name. Each has ``UNIT`` and
``read(facts) -> float | None`` (``facts``: ``bench.harness.Facts``); a
reader that finds nothing to read returns None and the metric is left out
of the result line."""
