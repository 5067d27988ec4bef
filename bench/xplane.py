"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone.  Device planes
are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation that ran.  Host threads carry the benchmark's own
``TraceAnnotation`` spans (names starting ``bench.``) on the same clock.

* busy: the union of the op intervals of each device, inside the
  measured window (the ``bench.window`` span), averaged over devices;
* kernel time: the summed durations of the ops whose name or HLO text
  names a kernel, with the operand shapes read from that HLO text;
* breakdown: the ops that took most time inside the window, and the
  longest idle gaps of the first device, each labelled with the host
  spans open at its midpoint.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
_SHAPE = re.compile(r"\b(pred|s8|u8|s32|u32|bf16|f16|f32|s64|f64)"
                    r"\[([0-9,]*)\]")


@dataclass
class Op:
    name: str
    start: float          # seconds on the trace clock
    dur: float
    text: str             # HLO text or op name: what kernels are matched on


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)     # id -> [Op]
    spans: list = field(default_factory=list)       # [(name, start, end)]
    window: tuple = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _stat_text(ev) -> str:
    parts = [ev.name]
    for k, v in dict(ev.stats).items():
        if isinstance(v, str) and k in ("long_name", "hlo_op", "tf_op",
                                        "hlo_module", "name"):
            parts.append(v)
    return " ".join(parts)


def load(path: str) -> Trace:
    """Read ``path`` (a file, or a directory holding one trace)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.name, ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9, _stat_text(ev)))
            ops.sort(key=lambda o: o.start)
            tr.devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        tr.spans.append((ev.name, ev.start_ns * 1e-9,
                                         ev.end_ns * 1e-9))
    wins = [(s, e) for n, s, e in tr.spans if n == WINDOW]
    if wins:
        tr.window = (min(s for s, _ in wins), max(e for _, e in wins))
    return tr


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy(tr: Trace, device: int) -> tuple:
    """(busy seconds, merged [start, end] intervals) of ``device``'s ops
    inside the window."""
    lo, hi = tr.window
    iv = [(max(o.start, lo), min(o.start + o.dur, hi))
          for o in tr.devices.get(device, [])]
    merged = _union([(s, e) for s, e in iv if e > s])
    return sum(e - s for s, e in merged), merged


def busy_s(tr: Trace, devices) -> float:
    """Busy seconds averaged over ``devices``."""
    return float(np.mean([busy(tr, d)[0] for d in devices]))


def kernel_ops(tr: Trace, device: int, pattern: str) -> list:
    """Ops of ``device`` inside the window whose text matches ``pattern``."""
    lo, hi = tr.window
    rx = re.compile(pattern)
    return [o for o in tr.devices.get(device, [])
            if lo <= o.start < hi and rx.search(o.text)]


def operand_shapes(text: str) -> list:
    """[(dtype, shape)] of the results and operands of one HLO instruction
    (its text up to its attributes, which repeat the operand types)."""
    head = text.split(", custom_call_target=")[0]
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _SHAPE.findall(head)]


def short_name(text: str) -> str:
    """An op's instruction name and result type, from its HLO text."""
    name, _, rest = text.partition(" = ")
    first = _SHAPE.search(rest)
    result = f"{first.group(1)}[{first.group(2)}]" if first else ""
    return f"{name.lstrip('%')} {result}".strip()[:120]


def _label(spans: list, t: float) -> str:
    names = sorted({n for n, s, e in spans if s <= t < e and n != WINDOW})
    return "+".join(n[len("bench."):] for n in names) or "none"


def breakdown(tr: Trace, device: int, top: int = 10) -> dict:
    """The ``top`` ops by summed time and the ``top`` longest idle gaps,
    each gap labelled with the host spans open at its midpoint."""
    lo, hi = tr.window
    per: dict = {}
    for o in tr.devices.get(device, []):
        inside = min(o.start + o.dur, hi) - max(o.start, lo)
        if inside > 0:
            n = short_name(o.name)
            per[n] = per.get(n, 0.0) + inside
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    _, merged = busy(tr, device)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"device_ops": [[n, float(s)] for n, s in ops],
            "idle_gaps": [[_label(tr.spans, (s + e) / 2), float(e - s)]
                          for s, e in gaps[:top]]}
