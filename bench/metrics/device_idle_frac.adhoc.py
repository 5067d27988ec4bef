"""Share (%) of the traced window in which no operation ran on the chip."""
from bench import lib


def read(run):
    return lib.idle_pct(run)
