"""One reader per per-layer metric, ``<metric name>.py``, each defining
``read(record) -> float | None``.  A reader that finds nothing to read
returns None and the harness leaves the metric out of the result line."""
