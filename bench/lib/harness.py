"""One run of one cell: set-up, the measured window, the metrics, and the
comparison with the plain reference that decides ``correct``.

Set-up builds the system's round engine (``build.py``), makes the initial
state from the seed, and drives the first two chunks through the window's
own ``DeviceEngine.chunk`` call and stream decode: the first compiles (or
loads the compiled chunk from the persistent cache), and is the chunk the
reference checks.  The window then runs whole chunks back to back, as
``run_scenario_device`` does: dispatch the chunk, pull its ``RoundStream``
to the host and decode it with the program's ``_unpack_stream``; no
evaluation, metrics file or checkpoint.  It closes with the first chunk
that ends past ``--seconds``.  Nothing compiles inside it (counted).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import json
import sys
import tempfile
import time

import jax
import numpy as np

from . import compare, scopes, trace as trace_lib
from .build import build_engine, make_inputs
from .reference import run_reference
from .spec import Cell, load_cell


# Seconds at the start of the window that a --trace 1 run traces.
TRACE_SECONDS = 5.0


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""
    cell: Cell
    device_kind: str
    setup_s: float
    window_s: float
    rounds: int
    chunk_ms: list
    sync_s: float
    peak_bytes: int | None
    trace: dict | None = None     # trace.reduce's summary (--trace 1)
    events: dict | None = None    # scopes.load's events (--trace 1)


class CompileCounter:
    """Counts tracing, lowering and backend compiles as JAX reports them."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, *args, **kwargs):
        if event.startswith("/jax/core/compile/"):
            self.n += 1


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def keep_compiled() -> None:
    """The program's persistent compilation cache
    (``repro.launch.compile_cache``), keeping every program however short
    its compile, so that a run after the first compiles nothing."""
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chip_error(devices, chips: int) -> str | None:
    if devices[0].platform != "tpu":
        return (f"needs a TPU, JAX found {devices[0].platform!r} "
                f"({devices[0].device_kind}); nothing was run")
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devices)}"
    return None


def drive_chunk(engine, carry, t0: int, size: int):
    """One chunk as users drive it: dispatch, pull, decode.  Returns the
    new carry, the decoded stream and the seconds spent in ``sync``: the
    host's share, the pull once the chunk is done (``wait``) and the
    decode."""
    from repro.sim.engine import _unpack_stream

    ts = jax.device_put(np.arange(t0, t0 + size, dtype=np.int32))
    with jax.profiler.TraceAnnotation("dispatch"):
        carry, out = engine.chunk(carry, ts)
    with jax.profiler.TraceAnnotation("wait"):
        jax.block_until_ready(out)
    s0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("sync"):
        stream = _unpack_stream(jax.tree.map(np.asarray, out),
                                engine.n_clients)
    return carry, stream, time.perf_counter() - s0


def first_chunk(engine, seed: int, size: int):
    """The initial state from ``seed`` and the first chunk driven from it;
    returns the carry and what the comparison reads of the chunk."""
    from repro.core.strategies import strategy_rates

    carry = engine.init_carry(jax.random.PRNGKey(seed))
    params0 = jax.tree.map(np.asarray, carry.params)
    carry, stream, _ = drive_chunk(engine, carry, 0, size)
    return carry, dict(stream=stream, params0=params0,
                       params=jax.tree.map(np.asarray, carry.params),
                       r=np.asarray(strategy_rates(engine.strategy,
                                                   carry.algo_state)))


def _failed_rounds(stream) -> int:
    """Rounds whose loss is not finite or whose cohort is not
    min(K_t, |A_t|) clients: checked on every round of the window."""
    size = stream.sel_mask.sum(axis=1)
    want = np.minimum(stream.k_t, stream.n_available)
    return int((~np.isfinite(stream.train_loss) | (size != want)).sum())


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, wrap_engine=None, loss=None) -> dict:
    """One run; returns the result line's object.  ``wrap_engine`` and
    ``loss`` plant faults under the timed path (used by the tests)."""
    from repro.core.strategies import select_path

    counter = CompileCounter()
    devices = jax.devices()[:cell.chips]
    inputs = make_inputs(cell)
    engine = build_engine(cell, inputs, loss=loss)
    if wrap_engine is not None:
        engine = wrap_engine(engine)
    size = cell.chunk_size
    carry, first = first_chunk(engine, seed, size)
    carry, _, _ = drive_chunk(engine, carry, size, size)
    setup_s = time.perf_counter() - t_start

    tmp = tempfile.TemporaryDirectory() if trace else None
    tracing = trace
    if tracing:
        jax.profiler.start_trace(tmp.name)
        span = jax.profiler.TraceAnnotation("window")
        span.__enter__()
    compiles0 = counter.n
    t, chunk_ms, sync_s, failed, paused = 2 * size, [], 0.0, 0, 0.0
    w0 = time.perf_counter()
    while True:
        c0 = time.perf_counter()
        carry, stream, s = drive_chunk(engine, carry, t, size)
        c1 = time.perf_counter()
        sync_s += s
        chunk_ms.append(1e3 * (c1 - c0))
        failed += _failed_rounds(stream)
        t += size
        if tracing and c1 - w0 >= min(seconds, TRACE_SECONDS):
            # The trace covers the window's first seconds, so that it stays
            # small enough to read; writing it out is left out of the
            # window's time.
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
            paused = time.perf_counter() - c1
        if c1 - w0 - paused >= seconds:
            break
    window_s = c1 - w0 - paused
    compiles = counter.n - compiles0
    peak = _peak_bytes(devices)
    rounds = t - 2 * size
    run = Run(cell=cell, device_kind=devices[0].device_kind, setup_s=setup_s,
              window_s=window_s, rounds=rounds, chunk_ms=chunk_ms,
              sync_s=sync_s, peak_bytes=peak)
    log(json.dumps({"workload": cell.name, "seed": seed,
                    "select_path": select_path("xla", engine.n_clients),
                    "compiles_in_window": compiles, "rounds": rounds,
                    "chunks": len(chunk_ms)}))
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {}
    if trace:
        # The compiled text maps the trace's ops to the program's scopes;
        # fetched once the window's compiles and peak memory are read.
        text = scopes.chunk_text(engine, carry, size)
        run.events = scopes.load(tmp.name, text,
                                 cell.config.get("scopes", ()))
        tmp.cleanup()
        summary = trace_lib.reduce(run.events)
        run.trace = summary
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}

    del engine, carry, stream
    gc.collect()
    jax.clear_caches()
    ref = run_reference(cell, inputs, seed, size)
    values = compare.numbers(first, ref)
    values["compiles_in_window"] = compiles
    correct, checks = compare.judge(values, cell.limits)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = importlib.import_module(f"bench.metrics.{m['name']}").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    return {"correct": correct, "attempted": rounds, "failed": failed,
            "metrics": metrics, "device": device, **result, "checks": checks}


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, *, t_start: float) -> int:
    args = parse(argv)
    try:
        cell = load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        log(f"bench: {e}")
        return 2
    err = chip_error(jax.devices(), cell.chips)
    if err:
        log(f"bench: {err}")
        return 1
    keep_compiled()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start)
    print(json.dumps(result), flush=True)
    return 0
