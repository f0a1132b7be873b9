"""Whole render: nominal FLOP of the rays delivered in the window over
the chips' bf16 peak for the window."""
from bench.readings import mfu_window


def read(run):
    return mfu_window(run)
