"""Host tile path: coalesce + scatter engine spans, mean ms per tile."""
from bench.readings import host_ms_per_tile


def read(run):
    return host_ms_per_tile(run)
