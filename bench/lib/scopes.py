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

A configuration may list the scopes inside its model under ``"scopes"``
in ``bench/configs/<config>.json``; those names are kept where they fall
beneath ``local_sgd`` (``local_sgd/mamba``), so a new model brings its
layers as data.

The host marks the chunk boundary with ``TraceAnnotation`` spans whose
keyword counters arrive as the event's stats: ``chunk_dispatch``
(``rounds``), ``stream_decode`` (``clients``, ``bytes``), ``stream_pull``
(``bytes``), and ``eval``, ``metrics_write``, ``checkpoint``.

``load`` gives ``trace.reduce``'s two keys (``devices``, ``host_spans``)
and adds ``scopes`` (one per device op) and ``spans`` (every host span
with its stats).  The reductions below work on those lists alone; a
per-layer metric's reader is one call of :func:`layer_ms_per_round`,
:func:`span_ms_per_round` or :func:`roofline_pct`.
"""
from __future__ import annotations

import collections
import glob
import re
import sys

from . import trace

# The program's layer names, spelled out here so that a rename in the
# program shows as a layer that goes missing.
SCOPES = ("avail", "budget", "select", "topk", "complete", "cohort",
          "stream", "local_sgd", "aggregate", "server_update", "collective")
PROGRAM_SPANS = ("chunk_dispatch", "stream_decode", "stream_pull", "eval",
                 "metrics_write", "checkpoint")
UNSCOPED = "unscoped"
NO_SPAN = "none"
_DEVICE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"

# Per-round device time of a layer (``bench/layers.py``'s table): the
# scopes under these heads.
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


def scope_of(op_name: str | None, model=()) -> str:
    """The run of layer names in an ``op_name`` path; ``collective`` keeps
    the mesh axis after it (``collective/clients``), and a name of
    ``model`` (a configuration's ``"scopes"``) counts beneath ``local_sgd``
    only."""
    parts = (op_name or "").split("/")
    out, i = [], 0
    while i < len(parts):
        if parts[i] == "collective" and i + 1 < len(parts):
            out.append(f"collective/{parts[i + 1]}")
            i += 2
            continue
        if parts[i] in SCOPES or (parts[i] in model and "local_sgd" in out):
            out.append(parts[i])
        i += 1
    return "/".join(out) or UNSCOPED


def chunk_text(engine, carry, size: int) -> str | None:
    """The compiled chunk program's text, for the instruction -> op_name
    map where the trace's op events carry no op_name."""
    import jax
    import numpy as np

    ts = jax.device_put(np.arange(size, dtype=np.int32))
    try:
        return engine._chunk.lower(carry, ts,
                                   engine._k_max_dev).compile().as_text()
    except Exception as e:   # the trace's own stats may still name the ops
        print(f"scopes: no compiled text of the chunk: {e!r}",
              file=sys.stderr, flush=True)
        return None


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


def load(trace_dir: str, hlo_text: str | None = None, model=()) -> dict:
    """Each device's ops (the ``XLA Ops`` line of every TPU plane) with
    their scopes, the harness's host spans, and every host span (harness
    and program) with its stats.  ``model``: the configuration's
    ``"scopes"``.  On the CPU, which has no device plane, the ops are the
    host events that name an ``hlo_op``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {paths}")
    module, by_instr = hlo_op_names(hlo_text) if hlo_text else (None, {})
    model = frozenset(model)
    names = set(trace.SPANS) | set(PROGRAM_SPANS)
    devices, scopes, spans, cpu_ops = {}, {}, [], []
    for plane in ProfileData.from_file(paths[0]).planes:
        if _DEVICE.fullmatch(plane.name):
            # An op event's name is its instruction's whole text, so the
            # stats, slow to read, are read once per instruction.
            ops, sc, by_name = [], [], {}
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    ops.append((name, ev.start_ns, ev.end_ns))
                    if name not in by_name:
                        by_name[name] = scope_of(
                            _op_name(name, _stats(ev), module, by_instr),
                            model)
                    sc.append(by_name[name])
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
                                                          module, by_instr),
                                                 model)))
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


def _devices(events: dict) -> dict:
    devices = events["devices"]
    if not devices or not any(devices.values()):
        raise RuntimeError("the trace holds no device operation")
    return devices


def self_ns(events: dict) -> dict:
    """Self time by scope within the window (ns, mean over devices),
    largest first; worked out once and kept in ``events``."""
    if "_self_ns" not in events:
        lo, hi = window(events)
        devices = _devices(events)
        total = collections.Counter()
        for dev, ops in devices.items():
            total.update(self_time(ops, events["scopes"][dev], lo, hi))
        events["_self_ns"] = {k: v / len(devices)
                              for k, v in total.most_common()}
    return events["_self_ns"]


def layer_ms_per_round(events: dict, heads) -> float | None:
    """Device self time of the scopes under ``heads`` (a head and every
    scope beneath it: ``select`` takes ``select/topk``) per round of the
    window, in ms.  None where the trace has no such scope (a program
    without scopes) or no ``chunk_dispatch`` span counted rounds."""
    t = [v for k, v in self_ns(events).items()
         if any(k == h or k.startswith(h + "/") for h in heads)]
    rounds = rounds_in(events["spans"], *window(events))
    return 1e-6 * sum(t) / rounds if t and rounds else None


def span_ms_per_round(events: dict, name: str) -> float | None:
    """Host time in the spans called ``name`` per round of the window, in
    ms; None where the trace has no such span or counted no rounds."""
    lo, hi = window(events)
    rounds = rounds_in(events["spans"], lo, hi)
    if not rounds or not any(sp[0] == name for sp in events["spans"]):
        return None
    return 1e-6 * span_time(events["spans"], name, lo, hi) / rounds


def roofline_pct(events: dict, heads, flops: float, nbytes: float,
                 peak: dict) -> float | None:
    """A layer's share of the chip's roofline: the least time its work
    could take, the larger of ``flops`` over peak FLOP/s and ``nbytes``
    over peak HBM bytes/s (one round's, counted from shapes; ``peak`` from
    ``peaks.peak_of``), over its measured time per round
    (:func:`layer_ms_per_round`).  None where that is None."""
    least = max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    if least <= 0:
        raise ValueError("a roofline needs the layer's FLOPs or bytes")
    ms = layer_ms_per_round(events, heads)
    return None if not ms else 100.0 * least / (1e-3 * ms)


def summarize(events: dict) -> dict:
    """The window's rounds, busy time, self time by scope and idle time by
    span (ns, mean over devices), and the per-round layer times (ms) of
    ``LAYERS`` and the ``stream_decode`` spans, None where the trace lacks
    them (:func:`layer_ms_per_round`)."""
    lo, hi = window(events)
    devices = _devices(events)
    n = len(devices)
    idle, busy = collections.Counter(), 0.0
    for ops in devices.values():
        idle.update(idle_by_span(ops, events["spans"], lo, hi))
        busy += sum(e - s for s, e in trace.merge(
            [(s, e) for _, s, e in ops], lo, hi))
    idle = {k: v / n for k, v in idle.most_common()}
    layers = {metric: layer_ms_per_round(events, heads)
              for metric, heads in LAYERS.items()}
    layers["decode_ms_per_round"] = span_ms_per_round(events, "stream_decode")
    return {"window_ns": hi - lo, "rounds": rounds_in(events["spans"], lo, hi),
            "busy_ns": busy / n, "self_ns": self_ns(events), "idle_ns": idle,
            "layers": layers}


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
