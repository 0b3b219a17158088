"""On-chip benchmark of the serving system: see ``bench/run.py``."""
