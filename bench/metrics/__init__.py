"""One reader per metric, ``<metric name>.py`` with ``read(ctx)``; shared
arithmetic lives in ``bench/readers.py``."""
