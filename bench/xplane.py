"""Reduction of a profiler trace (`.xplane.pb`) to device busy time, idle
share, the device operations that took the most time and the longest idle
gaps, each named by the harness span open on the host during it.

A device plane is one named `/device:<TPU|GPU>:<n>`. Its operations are the
events of its "XLA Ops" line (all of its lines where it has none). The
traced window is the first host event named `bench.window`. Busy time is
the union of the operation intervals inside the window, averaged over the
device planes; the idle share is 1 - busy / window.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HLO_TEXT = re.compile(r"^(%[\w.\-]+) = .*?\b([a-z][a-z\-]*)\(")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path: str) -> dict:
    """{"devices": [[(name, start_ns, end_ns), ...] per device plane],
    "spans": [(name, start_ns, end_ns), ...] of the harness's host spans}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices.append([(op_name(ev.name), ev.start_ns, ev.end_ns)
                            for ln in ops for ev in ln.events])
        elif plane.name.startswith("/host:"):
            spans += [(ev.name, ev.start_ns, ev.end_ns)
                      for ln in plane.lines for ev in ln.events
                      if ev.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def op_name(text: str) -> str:
    """"<kind> <instruction>" of an HLO text event ("fusion %fusion.60"),
    else the event's own name."""
    m = HLO_TEXT.match(text)
    return f"{m.group(2)} {m.group(1)}" if m else text


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(spans, t):
    """Name of the shortest harness span (other than the window) open at
    time t, or "host"."""
    best = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t < e and (
                best is None or e - s < best[1]):
            best = (name[len(SPAN_PREFIX):], e - s)
    return best[0] if best else "host"


def reduce(trace: dict, top: int = 10) -> dict | None:
    """busy_s, window_s, idle_share (0-1), device_ops and idle_gaps (each
    a list of [name, seconds], longest first, at most `top`); None when the
    trace holds no window span or no device plane."""
    windows = [(s, e) for n, s, e in trace["spans"] if n == WINDOW_SPAN]
    if not windows or not trace["devices"]:
        return None
    w0, w1 = windows[0]
    busy, op_time, gaps = [], {}, []
    for i, events in enumerate(trace["devices"]):
        clipped = [(max(s, w0), min(e, w1), n) for n, s, e in events
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            op_time[n] = op_time.get(n, 0.0) + (e - s)
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        if i == 0:  # gaps of the first device, named by the host
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    n_dev = len(trace["devices"])
    window_ns = w1 - w0
    busy_ns = sum(busy) / n_dev
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": window_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "device_ops": [[n, t * 1e-9 / n_dev] for n, t in ops],
        "idle_gaps": [[_innermost(trace["spans"], (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps],
    }
