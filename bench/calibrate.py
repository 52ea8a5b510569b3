#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, at a cell's own
size, in one process on the chip:

    python3 bench/calibrate.py --workload <cell> --seeds 1:13 \\
        --control-seeds 101:104 --fault-seeds 201:204 [--out FILE]

* ``program``: the system's first chunk against the plain reference, one
  line per seed (the lower readings: the largest over the seeds);
* ``control``: the reference in bfloat16, put in the system's place
  (the upper readings: the smallest over the seeds); ``control_model``
  the same with only the model side in bfloat16;
* each fault of ``bench/lib/faults.py`` planted under the system.

With ``--stand-in`` it runs where no TPU is had: ``program`` is then the
reference with every matrix product, forward and backward, in one
bfloat16 pass (the TPU's default float32 product), standing in for the
system on the chip.

One JSON line per (kind, seed), then one ``summary`` line.  The benchmark's
own runs do not run this.
"""
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]


def seeds_of(text: str) -> list:
    if ":" in text:
        lo, hi = text.split(":")
        return list(range(int(lo), int(hi)))
    return [int(s) for s in text.split(",") if s]


def as_program(out: dict) -> dict:
    """A reference run in the system's place, as the comparison reads it."""
    stream = SimpleNamespace(sel_mask=out["sel"], completed=out["sel"],
                             k_t=out["k_t"], n_available=out["n_available"],
                             train_loss=out["loss"],
                             delta_norm=out["delta_norm"])
    return dict(stream=stream, params0=out["params0"], params=out["params"],
                r=out["r"])


def by_round(prog: dict, ref: dict) -> dict:
    """Each round's relative loss and update-norm gap, for the look at
    which rounds a gap comes from (not compared)."""
    import numpy as np

    def gaps(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return (np.abs(a - b) / np.abs(b)).tolist()

    s = prog["stream"]
    return {"loss_gap_by_round": gaps(s.train_loss, ref["loss"]),
            "update_norm_gap_by_round": gaps(s.delta_norm, ref["delta_norm"])}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1:13")
    ap.add_argument("--control-seeds", default="101:104")
    ap.add_argument("--fault-seeds", default="201:204")
    ap.add_argument("--out")
    ap.add_argument("--stand-in", action="store_true",
                    help="no chip: the reference in one-pass bfloat16 "
                         "products stands in for the program")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    from bench.lib import compare, faults
    from bench.lib.build import build_engine, make_inputs
    from bench.lib.harness import chip_error, first_chunk, keep_compiled
    from bench.lib.reference import run_reference
    from bench.lib.spec import load_cell

    cell = load_cell(args.workload)
    err = None if args.stand_in else chip_error(jax.devices(), cell.chips)
    if err:
        print(f"calibrate: {err}", file=sys.stderr)
        return 1
    keep_compiled()
    out = open(args.out, "w") if args.out else None
    readings = {}

    def emit(kind, seed, values, seconds, prog=None, want=None):
        extra = {} if prog is None else by_round(prog, want)
        line = json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                           "seconds": seconds, **values, **extra})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
        readings.setdefault(kind, []).append(values)

    refs = {}

    def ref(seed):
        if seed not in refs:
            refs[seed] = run_reference(cell, inputs, seed, cell.chunk_size)
        return refs[seed]

    inputs = make_inputs(cell)
    engine = build_engine(cell, inputs)
    runs = [] if args.stand_in else [("program", engine, args.seeds)]
    runs += [(name, wrap(engine), args.fault_seeds)
             for name, wrap in faults.ENGINE_FAULTS.items()]
    runs += [(name, build_engine(cell, inputs, loss=make(
                 cell.module.program_loss(cell.config))), args.fault_seeds)
             for name, make in faults.LOSS_FAULTS.items()]
    for kind, eng, seeds in runs:
        for seed in seeds_of(seeds):
            t0 = time.perf_counter()
            _, first = first_chunk(eng, seed, cell.chunk_size)
            emit(kind, seed, compare.numbers(first, ref(seed)),
                 time.perf_counter() - t0, first, ref(seed))
    if args.stand_in:
        for seed in seeds_of(args.seeds):
            t0 = time.perf_counter()
            prog = as_program(run_reference(cell, inputs, seed, cell.chunk_size,
                                            one_pass=True))
            emit("program", seed, compare.numbers(prog, ref(seed)),
                 time.perf_counter() - t0, prog, ref(seed))
    controls = {"control": None, "control_model": jnp.float32}
    for kind, select_dtype in controls.items():
        for seed in seeds_of(args.control_seeds):
            t0 = time.perf_counter()
            ctrl = run_reference(cell, inputs, seed, cell.chunk_size,
                                 dtype=jnp.bfloat16, select_dtype=select_dtype)
            emit(kind, seed, compare.numbers(as_program(ctrl), ref(seed)),
                 time.perf_counter() - t0, as_program(ctrl), ref(seed))
    summary = {kind: {name: (min if kind != "program" else max)(
                   v[name] for v in vals) for name in vals[0]}
               for kind, vals in readings.items()}
    line = json.dumps({"workload": cell.name, "kind": "summary", **summary})
    print(line, flush=True)
    if out:
        out.write(line + "\n")
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
