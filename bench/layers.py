#!/usr/bin/env python3
"""Where one cell's round time goes, layer by layer, from a traced window:

    python3 bench/layers.py --workload <cell> --seed <n> [--seconds 5] \\
        [--chunks N] [--events FILE] [--xplane DIR]

The cell is built and its chunks are driven as ``bench/run.py`` drives
them (``harness.first_chunk``, ``harness.drive_chunk``): after two warm
chunks, a window of ``--seconds`` (or ``--chunks`` chunks) untraced, then
one of the same length under the profiler, in a ``window`` span as the
harness's ``--trace 1`` run has it.  The trace is read with
``bench/lib/scopes.py``: device self time by round scope, the device's
idle time by the innermost host span, and the per-round times of the
layers (``scopes.LAYERS`` and the ``stream_decode`` spans).  The tables go
to standard error; the last line of standard output is one JSON object
with both windows' rounds per second and the summary.  The untraced
window runs first, right after the warm chunks; on a TPU v5e it read the
small cell slower than the harness's untraced windows do, so the cost of
tracing is best read against those.  ``--events`` writes the trace's
events in ``scopes.strip``'s form, ``--xplane`` copies the profiler's
file.  It runs on the CPU too, where the ops are host events.  The
benchmark's own runs do not run it.
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def drive(engine, carry, t: int, size: int, seconds: float, chunks: int):
    """Chunks back to back until ``chunks`` are done, or else ``seconds``
    have passed; returns carry, next round, rounds and seconds."""
    from bench.lib.harness import drive_chunk

    t0, w0 = t, time.perf_counter()
    n = 0
    while True:
        carry, _, _ = drive_chunk(engine, carry, t, size)
        t += size
        n += 1
        elapsed = time.perf_counter() - w0
        if (n >= chunks) if chunks else (elapsed >= seconds):
            return carry, t, t - t0, elapsed


def measure(engine, seed: int, size: int, seconds: float = 5.0,
            chunks: int = 0, xplane: str | None = None, model=()):
    """Both windows and the trace's events; see the module docstring.
    ``model``: the configuration's ``"scopes"``."""
    import jax
    from bench.lib import scopes
    from bench.lib.harness import drive_chunk, first_chunk

    carry, _ = first_chunk(engine, seed, size)
    carry, _, _ = drive_chunk(engine, carry, size, size)
    text = scopes.chunk_text(engine, carry, size)
    carry, t, plain_rounds, plain_s = drive(engine, carry, 2 * size, size,
                                            seconds, chunks)
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("window"):
            carry, t, traced_rounds, traced_s = drive(engine, carry, t, size,
                                                      seconds, chunks)
        jax.profiler.stop_trace()
        if xplane:
            for path in Path(tmp).rglob("*.xplane.pb"):
                Path(xplane).mkdir(parents=True, exist_ok=True)
                shutil.copy(path, Path(xplane) / path.name)
        events = scopes.load(tmp, text, model)
    rates = {"untraced": plain_rounds / plain_s,
             "traced": traced_rounds / traced_s}
    return events, rates


def report(events: dict) -> dict:
    """The summary, with the tables on standard error."""
    from bench.lib import scopes, trace

    summary = scopes.summarize(events)
    rounds, busy = summary["rounds"], summary["busy_ns"]
    log = dict(file=sys.stderr, flush=True)
    print(scopes.table(f"device self time by scope ({rounds} rounds, busy "
                       f"{1e-6 * busy:.3f} ms)", summary["self_ns"], rounds,
                       busy), **log)
    idle = summary["window_ns"] - busy
    print(scopes.table(f"device idle time by innermost host span (idle "
                       f"{1e-6 * idle:.3f} ms)", summary["idle_ns"], rounds,
                       idle), **log)
    for name, value in summary["layers"].items():
        print(f"{name} = {value!r}", **log)
    summary["reduce"] = {k: v for k, v in trace.reduce(events).items()
                         if k in ("busy_s", "window_s")}
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--chunks", type=int, default=0)
    ap.add_argument("--events", help="write the stripped events here")
    ap.add_argument("--xplane", help="copy the profiler's file here")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench.lib import scopes
    from bench.lib.build import build_engine, make_inputs
    from bench.lib.harness import keep_compiled
    from bench.lib.spec import load_cell

    cell = load_cell(args.workload)
    keep_compiled()
    engine = build_engine(cell, make_inputs(cell))
    events, rates = measure(engine, args.seed, cell.chunk_size, args.seconds,
                            args.chunks, args.xplane,
                            cell.config.get("scopes", ()))
    if args.events:
        Path(args.events).parent.mkdir(parents=True, exist_ok=True)
        with open(args.events, "w") as f:
            json.dump(scopes.strip(events), f, separators=(",", ":"))
    summary = report(events)
    device = jax.devices()[0]
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "device": device.device_kind,
                      "rounds_per_s": rates, **summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
