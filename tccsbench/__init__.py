"""On-chip benchmark of TCCS serving; see ``run.py``."""
