"""On-chip benchmark of the graph analytics path; see ``bench/run.py``."""
