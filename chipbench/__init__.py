"""The chip benchmark of this repository: harness, drivers, plain reference,
trace reduction, cost models and per-layer metric readers. See ``run.py``."""
