"""The yardstick: everything here is general. No cell, configuration
or traffic-mix name appears in this package or in ``run.py``; they
live in ``BENCHMARK.json`` and in the data files it names."""
