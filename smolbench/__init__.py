"""smolbench: the chip benchmark of the served path (see run.py)."""
