"""Per-layer reading of a profiler trace: device time by the round
program's named scopes, and idle time by the host span it fell in.

The program (``repro.core.spans``) wraps each layer of the round in a
``jax.named_scope``, so every XLA op's ``op_name`` carries a path such as
``jit(chunk)/while/body/closed_call/select/topk/jit(argsort)/sort``.  An
op's *scope* is the run of the layer names below in that path
(``select/topk``); an op under none of them is ``unscoped`` (the scan's own
loop, carry copies).  A fusion carries the ``op_name`` of its root op.  The
path comes from the op event's own stat where the profiler records one,
else from the compiled program's text (instruction name -> ``op_name``).

The host marks the chunk boundary with ``TraceAnnotation`` spans whose
keyword counters arrive as the event's stats: ``chunk_dispatch``
(``rounds``), ``stream_decode`` (``clients``, ``bytes``), ``stream_pull``
(``bytes``), and ``eval``, ``metrics_write``, ``checkpoint``.

``load`` keeps ``trace.load``'s two keys (``devices``, ``host_spans``), so
``trace.reduce`` reads the same dict, and adds ``scopes`` (one per device
op) and ``spans`` (every host span with its stats).  The reductions below
work on those lists alone.
"""
from __future__ import annotations

import collections
import glob
import re

from . import trace

# The program's layer names, spelled out here so that a rename in the
# program shows as a layer that goes missing.
SCOPES = ("avail", "budget", "select", "topk", "complete", "cohort",
          "stream", "local_sgd", "aggregate", "server_update", "collective")
PROGRAM_SPANS = ("chunk_dispatch", "stream_decode", "stream_pull", "eval",
                 "metrics_write", "checkpoint")
UNSCOPED = "unscoped"
NO_SPAN = "none"

# Per-round device time of a layer: the scopes whose path starts with one
# of these names.
LAYERS = {
    "avail_ms_per_round": ("avail", "budget"),
    "select_ms_per_round": ("select",),
    "cohort_ms_per_round": ("cohort",),
    "local_sgd_ms_per_round": ("local_sgd",),
    "aggregate_ms_per_round": ("aggregate", "server_update"),
}
# Stats of an op event that may carry its op_name, in order of trust.
_OP_NAME_STATS = ("tf_op", "op_name")
_INSTR = re.compile(r"%?([\w.\-]+)")
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name=\"([^\"]*)\"",
    re.M)
_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def scope_of(op_name: str | None) -> str:
    """The run of layer names in an ``op_name`` path; ``collective`` keeps
    the mesh axis after it (``collective/clients``)."""
    parts = (op_name or "").split("/")
    out, i = [], 0
    while i < len(parts):
        if parts[i] == "collective" and i + 1 < len(parts):
            out.append(f"collective/{parts[i + 1]}")
            i += 2
            continue
        if parts[i] in SCOPES:
            out.append(parts[i])
        i += 1
    return "/".join(out) or UNSCOPED


def hlo_op_names(hlo_text: str) -> tuple[str | None, dict]:
    """Module name and instruction name -> ``op_name`` of a compiled
    program's text (``jax.stages.Compiled.as_text()``)."""
    module = _HLO_MODULE.search(hlo_text)
    return (module.group(1) if module else None,
            dict(_HLO_LINE.findall(hlo_text)))


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats if not k.startswith("_")}


def _op_name(name: str, stats: dict, module: str | None,
             by_instr: dict) -> str | None:
    for key in _OP_NAME_STATS:
        if stats.get(key):
            return str(stats[key])
    if module and stats.get("hlo_module") not in (None, module):
        return None
    instr = stats.get("hlo_op") or _INSTR.match(name).group(1)
    return by_instr.get(str(instr))


def load(trace_dir: str, hlo_text: str | None = None) -> dict:
    """``trace.load``'s device ops and harness spans, plus each op's scope
    and every host span (harness and program) with its stats.  On the CPU,
    which has no device plane, the ops are the host events that name an
    ``hlo_op``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {paths}")
    module, by_instr = hlo_op_names(hlo_text) if hlo_text else (None, {})
    names = set(trace.SPANS) | set(PROGRAM_SPANS)
    devices, scopes, spans, cpu_ops = {}, {}, [], []
    for plane in ProfileData.from_file(paths[0]).planes:
        if trace._DEVICE.fullmatch(plane.name):
            ops, sc = [], []
            for line in plane.lines:
                if line.name != trace.OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns, ev.end_ns))
                    sc.append(scope_of(_op_name(ev.name, _stats(ev), module,
                                                by_instr)))
            devices[plane.name], scopes[plane.name] = ops, sc
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        spans.append((ev.name, ev.start_ns, ev.end_ns,
                                      _stats(ev)))
                        continue
                    stats = _stats(ev)
                    if "hlo_op" in stats:
                        cpu_ops.append((ev.name, ev.start_ns, ev.end_ns,
                                        scope_of(_op_name(ev.name, stats,
                                                          module, by_instr))))
    if not devices and cpu_ops:
        devices["/host:CPU"] = [op[:3] for op in cpu_ops]
        scopes["/host:CPU"] = [op[3] for op in cpu_ops]
    return {"devices": devices, "scopes": scopes,
            "host_spans": [s[:3] for s in spans if s[0] in trace.SPANS],
            "spans": spans}


def window(events: dict) -> tuple[float, float]:
    """The last ``window`` span, as ``trace.reduce`` takes it."""
    windows = [(s, e) for name, s, e, *_ in events["spans"]
               if name == "window"]
    if not windows:
        raise RuntimeError("the trace holds no 'window' span")
    return windows[-1]


def self_time(ops, scopes, lo: float, hi: float) -> collections.Counter:
    """Device time by scope within [lo, hi], each op counted for its own
    time only: its duration less the part its nested ops cover (so a loop
    and the ops of its body are not counted twice).  The values sum to the
    union of the op intervals when ops nest on the device's timeline."""
    items = sorted(((max(s, lo), min(e, hi), sc)
                    for (_, s, e), sc in zip(ops, scopes)
                    if min(e, hi) > max(s, lo)),
                   key=lambda x: (x[0], -x[1]))
    out = collections.Counter()
    stack = []   # [start, end, scope, covered, covered_until]

    def close(entry):
        out[entry[2]] += entry[1] - entry[0] - entry[3]

    for s, e, sc in items:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        # every open op covers what this one overlaps and its inner ops
        # have not covered yet (an op past its parent's end covers the
        # grandparent too)
        for entry in reversed(stack):
            c0, c1 = max(s, entry[4]), min(e, entry[1])
            if c1 > c0:
                entry[3] += c1 - c0
                entry[4] = c1
        stack.append([s, e, sc, 0.0, s])
    while stack:
        close(stack.pop())
    return out


def idle_by_span(ops, spans, lo: float, hi: float) -> collections.Counter:
    """Every idle nanosecond of the device in [lo, hi] under the shortest
    host span covering it (``window`` aside), or ``none``."""
    busy = trace.merge([(s, e) for _, s, e in ops], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = sorted((s, e, name) for name, s, e, *_ in spans
                  if name != "window" and e > s)
    out = collections.Counter()
    j, active = 0, []
    for a, b in idle:
        while j < len(host) and host[j][0] < b:
            active.append(host[j])
            j += 1
        active = [sp for sp in active if sp[1] > a]
        cuts = sorted({a, b} | {x for s, e, _ in active for x in (s, e)
                                if a < x < b})
        for x0, x1 in zip(cuts, cuts[1:]):
            cover = [sp for sp in active if sp[0] <= x0 and sp[1] >= x1]
            name = (min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover
                    else NO_SPAN)
            out[name] += x1 - x0
    return out


def span_time(spans, name: str, lo: float, hi: float) -> float:
    """Time of the spans called ``name`` within [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for n, s, e, *_ in spans if n == name)


def rounds_in(spans, lo: float, hi: float) -> int:
    """Rounds dispatched in [lo, hi): the ``rounds`` counters of the
    ``chunk_dispatch`` spans that start in it."""
    return int(sum(st.get("rounds", 0) for n, s, _, st in spans
                   if n == "chunk_dispatch" and lo <= s < hi))


def summarize(events: dict) -> dict:
    """The window's rounds, busy time, self time by scope and idle time by
    span (ns, mean over devices), and the per-round layer times (ms).  A
    layer whose scope the trace lacks (a program without scopes) reads
    None, as does everything per round when no ``chunk_dispatch`` span
    counted rounds."""
    lo, hi = window(events)
    devices = events["devices"]
    if not devices or not any(devices.values()):
        raise RuntimeError("the trace holds no device operation")
    n = len(devices)
    by_scope, idle, busy = collections.Counter(), collections.Counter(), 0.0
    for dev, ops in devices.items():
        by_scope.update(self_time(ops, events["scopes"][dev], lo, hi))
        idle.update(idle_by_span(ops, events["spans"], lo, hi))
        busy += sum(e - s for s, e in trace.merge(
            [(s, e) for _, s, e in ops], lo, hi))
    by_scope = {k: v / n for k, v in by_scope.most_common()}
    idle = {k: v / n for k, v in idle.most_common()}
    rounds = rounds_in(events["spans"], lo, hi)
    layers = {}
    for metric, heads in LAYERS.items():
        t = [v for k, v in by_scope.items() if k.split("/")[0] in heads]
        layers[metric] = (1e-6 * sum(t) / rounds if t and rounds else None)
    decode = any(sp[0] == "stream_decode" for sp in events["spans"])
    layers["decode_ms_per_round"] = (
        1e-6 * span_time(events["spans"], "stream_decode", lo, hi) / rounds
        if decode and rounds else None)
    return {"window_ns": hi - lo, "rounds": rounds, "busy_ns": busy / n,
            "self_ns": by_scope, "idle_ns": idle, "layers": layers}


def table(title: str, ns: dict, rounds: int, total: float) -> str:
    """Rows of name, ms per round and share of ``total``."""
    rows = [title]
    for k, v in ns.items():
        per = f"{1e-6 * v / rounds:10.4f}" if rounds else "       n/a"
        rows.append(f"  {k:<40s}{per} ms/round {100 * v / total:6.2f}%")
    return "\n".join(rows)


def strip(events: dict, lo: float | None = None,
          hi: float | None = None) -> dict:
    """``events`` as JSON-ready lists, with times in whole ns relative to
    ``lo`` and ops outside [lo, hi] dropped: the form kept as test data."""
    lo = window(events)[0] if lo is None else lo
    hi = window(events)[1] if hi is None else hi
    devices, scopes = {}, {}
    for dev, ops in events["devices"].items():
        keep = [(op, sc) for op, sc in zip(ops, events["scopes"][dev])
                if op[2] > lo and op[1] < hi]
        devices[dev] = [[_INSTR.match(name).group(1), round(s - lo),
                         round(e - lo)] for (name, s, e), _ in keep]
        scopes[dev] = [sc for _, sc in keep]
    spans = [[name, round(s - lo), round(e - lo), st]
             for name, s, e, st in events["spans"] if e > lo and s < hi]
    return {"devices": devices, "scopes": scopes,
            "host_spans": [sp[:3] for sp in spans if sp[0] in trace.SPANS],
            "spans": spans}
