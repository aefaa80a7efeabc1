"""On-chip benchmark of the decode path; see ``bench/run.py``."""
