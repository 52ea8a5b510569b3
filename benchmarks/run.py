"""Benchmark harness — one function per paper table/figure + the systems
benches that the paper lacks (roofline, selection overhead, kernel paths).

  python -m benchmarks.run                  # quick CPU-scale pass of all
  python -m benchmarks.run --only tables23  # one benchmark
  python -m benchmarks.run --full           # paper-scale rounds (slow)

Prints ``name,us_per_call,derived`` CSV rows (plus formatted tables).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "bench")


def bench_tables23(full: bool):
    from . import paper_tables
    rounds = 600 if full else 150
    seeds = (0, 1, 2) if full else (0,)
    res = paper_tables.run(rounds=rounds, seeds=seeds, out_dir=OUT_DIR)
    print(paper_tables.format_tables(res))


def bench_fig5(full: bool):
    from . import vary_k
    vary_k.run(ks=(2, 5, 10, 20) if full else (5, 10),
               rounds=400 if full else 150, out_dir=OUT_DIR)


def bench_table9(full: bool):
    from . import vary_alpha
    vary_alpha.run(rounds=400 if full else 150, out_dir=OUT_DIR)


def bench_scenarios(full: bool):
    from repro.sim.sweep import run_sweep
    scenarios = ("bernoulli", "markov", "gilbert_elliott", "diurnal", "drift",
                 "trace", "bandwidth", "stepk") if full else \
                ("bernoulli", "markov", "diurnal")
    run_sweep(scenarios, ("f3ast", "fedavg"),
              rounds=300 if full else 60,
              out_dir=os.path.join(OUT_DIR, "scenario_sweep"))


def bench_selection(full: bool):
    from . import selection_overhead
    if full:
        os.makedirs(OUT_DIR, exist_ok=True)
        selection_overhead.run(
            ns=selection_overhead.BASELINE_NS,
            out=os.path.join(OUT_DIR, "BENCH_selection.json"))
    else:
        selection_overhead.run(ns=(100, 10_000))


def bench_kernels(full: bool):
    from . import kernels_bench
    kernels_bench.run()


def bench_roofline(full: bool):
    from . import roofline
    roofline.run()


def bench_engine(full: bool):
    from . import bench_engine as eng
    out = os.path.join(OUT_DIR, "BENCH_engine.json")
    os.makedirs(OUT_DIR, exist_ok=True)
    eng.main(([] if full else ["--quick"]) + ["--out", out])


BENCHES = {
    "tables23": bench_tables23,
    "fig5": bench_fig5,
    "table9": bench_table9,
    "scenarios": bench_scenarios,
    "selection": bench_selection,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
    "engine": bench_engine,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    names = [args.only] if args.only else list(BENCHES)
    for name in names:
        print(f"\n===== bench: {name} =====")
        BENCHES[name](args.full)


if __name__ == "__main__":
    main()
