"""Median TurnSpans.l2_s (ms): the wave's time inside the L2 tier, its
probe and its admission flush."""
from bench import lib


def read(run):
    return lib.percentile(lib.spans_ms(run, "l2_s"), 50)
