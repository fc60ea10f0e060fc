"""Metric readers: ``<metric>.py`` holds ``read(run) -> float | None``.

``run`` is a :class:`bench.harness.Run`.  A reader that finds nothing to
read returns None, and the harness leaves that metric out of the line;
no share of a roofline or a peak is ever reported as 0 for want of
data.
"""
