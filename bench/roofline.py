"""Peaks per device kind, and the operations and bytes of each kernel call.

``peaks.json`` holds the published per-chip peaks keyed by JAX's
``device_kind``; a device that is not in it is an error.  A kernel's
least time is the larger of its operations over the peak rate and its
bytes over the HBM bandwidth; its roofline share is that least time over
the time the trace shows it took.  Operations and bytes are worked out
from the shapes the call was given, as the algorithm needs them.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

PEAKS = pathlib.Path(__file__).with_name("peaks.json")
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
            "u32": 4, "f32": 4, "s64": 8, "f64": 8}


def peaks(device_kind: str, path=PEAKS) -> dict:
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def nbytes(shapes) -> int:
    """Bytes of [(dtype, shape)] arrays."""
    return int(sum(ITEMSIZE[t] * int(np.prod(s, dtype=np.int64))
                   for t, s in shapes))


def knn_fused_topk(shapes) -> tuple:
    """(flops, bytes) of one ``knn_fused_topk`` call from its HLO operand
    and result shapes: the corpus (n, d) payload, its ids and scales, the
    (b, d) query block and the (b, k) answers are each moved once; the
    scores are 2 * b * n * d operations."""
    two_d = [(t, s) for t, s in shapes if len(s) == 2]
    corpus = max(two_d, key=lambda ts: ts[1][0] * ts[1][1])
    n, d = corpus[1]
    b = max(s[0] for t, s in two_d if s[1] == d and s != corpus[1]) \
        if any(s[1] == d and s != corpus[1] for _, s in two_d) else 1
    return 2.0 * b * n * d, float(nbytes(shapes))


def probe_rhat_batched(shapes) -> tuple:
    """(flops, bytes) of one batched LowQuality probe: every session's
    (q_max, d) recorded queries against its query, 2 * s * q_max * d."""
    three_d = [s for _, s in shapes if len(s) == 3]
    s, q_max, d = max(three_d, key=lambda x: x[0] * x[1] * x[2])
    return 2.0 * s * q_max * d, float(nbytes(shapes))


def share(calls: list, seconds: float, peak: dict) -> tuple:
    """(share in %, bound) over ``calls`` [(flops, bytes)] that took
    ``seconds`` in all; bound is ``memory`` or ``compute`` by which of
    the two least times is larger summed over the calls."""
    if seconds <= 0 or not calls:
        return None, None
    t_c = sum(f for f, _ in calls) / peak["bf16_flops_per_s"]
    t_m = sum(b for _, b in calls) / peak["hbm_bytes_per_s"]
    least = sum(max(f / peak["bf16_flops_per_s"],
                    b / peak["hbm_bytes_per_s"]) for f, b in calls)
    return 100.0 * least / seconds, ("memory" if t_m >= t_c else "compute")
