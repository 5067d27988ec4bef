"""Turns completed per second of a closed loop's window (whole bursts)."""
import numpy as np

from bench import traffic


def read(run):
    got = traffic.completion_rate(
        np.asarray([t.done for t in run.turns]),
        run.window[0] + run.seconds, float(run.mix["burst_gap_s"]))
    return None if got is None else got[0] / got[1]
