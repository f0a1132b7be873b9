"""Host tile path: engine steps less the waits on the chip (engine
spans), mean ms per tile."""
from bench.program_spans import step_host_ms_per_tile


def read(run):
    return step_host_ms_per_tile(run)
