"""95th percentile latency of the requests due in the window."""
from bench.readings import latency_ms


def read(run):
    return latency_ms(run, 95)
