"""Metric readers, one module per quantity.

The harness looks for ``metrics/<name>.py`` and then for the part of the
name before its first dot (``search_ms.open`` -> ``search_ms.py``).  Each
module exposes ``read(ctx) -> float | None``; ``ctx`` is the run record
built in ``bench/run.py`` (``RunContext``).  A reader that finds nothing to
read returns ``None`` and the metric is left out of the result line.
"""
