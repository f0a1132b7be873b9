"""Fused two-pass kernel: share of its roofline on the device trace."""
from bench.readings import kernel_roofline


def read(run):
    return kernel_roofline(run)
