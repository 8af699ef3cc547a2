"""Harness of the on-chip LAGS-SGD training benchmark.

``run.py`` beside this package is the entry point.  The modules here are
the yardstick: they read the cell's data files, make the weights and the
token feed from the seed, drive the program's training loop, reduce the
device trace, and decide ``correct`` against the plain float32
reference.  Nothing here imports the program's model, exchange or
optimizer code; the program is driven through ``repro.api.Session``.
"""
