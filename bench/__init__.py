"""The benchmark of the PB graph engine: see ``bench/run.py``."""
