"""The on-chip benchmark: harness, traffic, reference and trace reduction.

Everything the yardstick is made of lives here, where a PR that claims a gain
cannot change it. ``run.py`` is the one command; ``BENCHMARK.json`` at the
root of the repo names the cells and metrics.
"""
