"""The on-chip benchmark: one cell per run (`python3 bench/run.py`)."""
