"""Benchmark of the ICARUS NeRF serving path on the chip (see run.py)."""
