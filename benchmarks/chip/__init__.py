"""The chip benchmark: cells, configurations, traffic, metrics and the
yardstick (work, peaks, reference, trace reduction), driven by
``BENCHMARK.json`` at the root of the checkout (see ``run.py``)."""
