#!/usr/bin/env python3
"""Benchmark of the federated round engine on a TPU: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cells, their configurations, traffic
mixes and metrics are declared in ``BENCHMARK.json``.  The last line of
standard output is one JSON object: ``correct``, ``attempted`` and
``failed`` rounds, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown`` of device operations and idle gaps, and last
the ``checks`` that decided ``correct``, each number beside its limit
(also the last lines of standard error).  Without a TPU, with fewer chips
than the cell asks for, or outside a checkout of the repository, it exits
non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no repository around {ROOT} (src/repro is missing); "
              f"run it from a checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib.harness import main as run
    return run(sys.argv[1:], t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
