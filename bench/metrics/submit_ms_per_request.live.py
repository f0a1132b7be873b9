"""Request intake: mean engine.submit span (admission and the request's
camera rays) per request submitted in the window, ms."""
from bench.program_spans import submit_ms_per_request


def read(run):
    return submit_ms_per_request(run)
