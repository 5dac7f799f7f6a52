"""The chip benchmark: harness, yardstick and cell data (see run.py)."""
