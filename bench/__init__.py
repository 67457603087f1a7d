"""The chip benchmark of the PT sampler: ``python3 -m bench.run``."""
