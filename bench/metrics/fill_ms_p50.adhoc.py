"""Median TurnSpans.insert_s (ms): fused insert+query, write-back, flush."""
from bench import lib


def read(run):
    return lib.percentile(lib.spans_ms(run, "insert_s"), 50)
