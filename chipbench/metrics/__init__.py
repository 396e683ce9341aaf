"""Metric readers: ``<metric>.py`` with ``read(run)``, found by name."""
