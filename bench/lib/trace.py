"""From the profiler's trace of the window to device busy time, idle share,
top device operations and idle gaps by what the host was doing.

``reduce`` works on plain lists, as ``scopes.load`` reads them from the
``.xplane.pb``: each device's operation intervals (``devices``) and the
harness's own host spans (``host_spans``: ``window``, ``dispatch``,
``wait``, ``sync``), so it is tested on hand-made and recorded events.
"""
from __future__ import annotations

import bisect
import collections

SPANS = ("window", "dispatch", "wait", "sync")


def merge(intervals, lo: float, hi: float) -> list:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(gap, spans) -> str:
    """The host span that overlaps ``gap`` most, or ``host``."""
    best, label = 0.0, "host"
    for name, s, e in spans:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best:
            best, label = overlap, name
    return label


def reduce(events: dict, top: int = 10) -> dict:
    """busy_s (mean over devices), window_s, device_ops, idle_gaps."""
    windows = [(s, e) for name, s, e in events["host_spans"] if name == "window"]
    if not windows:
        raise RuntimeError("the trace holds no 'window' span")
    lo, hi = windows[-1]
    devices = events["devices"]
    if not devices or not any(devices.values()):
        raise RuntimeError("the trace holds no device operation")
    busy, per_op = [], collections.Counter()
    for ops in devices.values():
        busy.append(sum(e - s for s, e in merge(
            [(s, e) for _, s, e in ops], lo, hi)))
        for name, s, e in ops:
            if min(e, hi) > max(s, lo):
                per_op[name] += min(e, hi) - max(s, lo)
    first = devices[sorted(devices)[0]]
    merged = merge([(s, e) for _, s, e in first], lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]),
                  key=lambda g: g[0] - g[1])[:top]
    host = sorted((s, e, name) for name, s, e in events["host_spans"]
                  if name != "window")
    starts = [s for s, _, _ in host]

    def near(gap):
        i = bisect.bisect_right(starts, gap[1])
        return [(name, s, e) for s, e, name in host[max(0, i - 64):i]]

    ns = 1e-9
    return {
        "busy_s": sum(busy) / len(busy) * ns,
        "window_s": (hi - lo) * ns,
        "device_ops": [[name, t * ns] for name, t in per_op.most_common(top)],
        "idle_gaps": [[_label(g, near(g)), (g[1] - g[0]) * ns] for g in gaps],
    }
