"""The chip benchmark: harness, traffic, reference, trace reduction and
metric readers. Entry point: ``python3 bench/run.py``."""
