"""One reader per metric, ``read(view) -> float | None`` (see view.py).

A reader that finds nothing to read returns None and the metric is left
out of the result line; it never returns 0 for a share of a peak.
"""
