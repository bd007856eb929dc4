"""Chip benchmark of the served spiking VGG9: see `bench/run.py`."""
