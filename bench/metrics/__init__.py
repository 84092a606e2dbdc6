"""One reader per per-layer metric: ``<name>.py`` with ``read(ctx)``.

``ctx`` is ``bench.harness.Context``.  A reader that finds nothing to read
returns None, and the metric is left out of the run's line.
"""
