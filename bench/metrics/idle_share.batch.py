"""Device: share of the traced window with no operation on the chip."""
from bench.readings import idle_share


def read(run):
    return idle_share(run)
