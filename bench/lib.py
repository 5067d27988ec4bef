"""Reductions the metric readers share (``bench/metrics/<name>.py``)."""

from __future__ import annotations

import numpy as np

from bench import roofline, xplane

# each kernel's op in the device trace: the custom call named after the
# jitted entry point that launches it
KERNELS = {
    "knn_fused_topk": r"^%knn_fused_topk(\.\d+)? = ",
    "probe_rhat_batched": r"^%probe_rhat_batched(\.\d+)? = ",
}


def percentile(values, q: float):
    v = np.asarray([x for x in values if np.isfinite(x)], np.float64)
    return float(np.percentile(v, q)) if v.size else None


def latencies_ms(run) -> list:
    return [1e3 * t.latency_s for t in run.turns]


def spans_ms(run, name: str) -> list:
    return [1e3 * getattr(t.result.spans, name) for t in run.answered()]


def backend_waves_ms(run) -> list:
    """``TurnSpans.backend_s`` once per wave that went to the back end
    (a wave's turns share their probe, back-end and fill spans)."""
    waves = {(t.result.spans.probe_s, t.result.spans.backend_s,
              t.result.spans.insert_s)
             for t in run.answered() if t.result.tier == "backend"}
    return [1e3 * w[1] for w in waves]


def roofline_share(run, kernel: str, cost):
    """A kernel's share (%) of its roofline over the traced window, or
    None where the trace holds no call of it."""
    if run.trace is None or not run.peak:
        return None
    ops = xplane.kernel_ops(run.trace, run.device, KERNELS[kernel])
    if not ops:
        return None
    calls = [cost(xplane.operand_shapes(o.text)) for o in ops]
    share, _bound = roofline.share(calls, sum(o.dur for o in ops), run.peak)
    return share


def idle_pct(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    busy, _ = xplane.busy(run.trace, run.device)
    return 100.0 * (1.0 - busy / run.trace.window_s)
