"""Median TurnSpans.backend_s (ms) over the waves that searched the corpus."""
from bench import lib


def read(run):
    return lib.percentile(lib.backend_waves_ms(run), 50)
