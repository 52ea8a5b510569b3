"""Scenario × strategy grid sweep with streaming JSONL metrics.

One command regenerates a paper-figure-style grid (Figs. 2–4 structure:
algorithms compared across availability/budget regimes):

    python -m repro.sim.sweep --scenarios bernoulli,markov,diurnal \
        --algorithms f3ast,fedavg --rounds 3

The grid is a base :class:`repro.sim.spec.RunSpec` crossed with
``dataclasses.replace`` per cell — each (scenario, strategy) cell runs from
one frozen spec, streams per-round records to
``<out>/<scenario>__<algorithm>.jsonl`` while it runs, and writes the spec
itself to ``<out>/<scenario>__<algorithm>.spec.json`` so any cell is
reproducible from that single artifact (``run_scenario(RunSpec.load(p))``).
A ``summary.json`` with every cell's final metrics is written at the end.
``--scenarios all`` sweeps the whole registry; ``--list`` prints the
registry and exits.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import Callable, Optional, Sequence

from ..launch.compile_cache import use_compile_cache
from .completion import COMPLETION_REGISTRY
from .runner import run_scenario
from .scenario import SCENARIO_REGISTRY, get_scenario, list_scenarios
from .spec import RunSpec

# universe for --algorithms all (fixed_f3ast is excluded: it needs an
# explicit r_target to differ from plain f3ast; fedavg_weighted is a
# variant of fedavg kept out of the default grid)
ALGORITHMS = ("f3ast", "fedavg", "fedadam", "poc", "uniform")


_UNSET = object()   # "kwarg not passed" — lets base_spec keep its value


def run_sweep(scenarios: Sequence[str], algorithms: Optional[Sequence[str]] = None,
              *, completions: Optional[Sequence[str]] = None,
              aggregations: Optional[Sequence[str]] = None,
              rounds=_UNSET, out_dir: str = "experiments/sweep",
              seed=_UNSET, server_opt=_UNSET, server_lr=_UNSET,
              eval_every: Optional[int] = None, engine=_UNSET,
              mesh_shape=_UNSET, clients_axis=_UNSET, model_axis=_UNSET,
              base_spec: Optional[RunSpec] = None,
              log_fn: Callable = print) -> dict:
    """Run the grid; returns {(scenario, algorithm): final_metrics} — with
    ``completions`` and/or ``aggregations`` given, the key tuple grows a
    completion / aggregation entry per extra axis.

    Every cell is ``dataclasses.replace(base_spec, scenario=...,
    strategy=..., ...)`` of one base :class:`RunSpec` — pass ``base_spec``
    to pin any other field (prox_mu, chunk_size, ...) across the grid; the
    loose keyword arguments cover the common ones and override the base
    only when explicitly passed.

    ``algorithms=None`` uses each scenario's own default grid.
    ``aggregations`` adds a server-semantics grid axis over
    ``("sync", "buffered")`` (DESIGN.md §7.4) — e.g. ``["sync",
    "buffered"]`` compares round-synchronous aggregation against the
    FedBuff-style buffered server cell by cell; ``None`` keeps every cell
    synchronous and the aggregation key out of the result tuple.
    ``completions`` adds a third grid axis of completion-process keys
    (``repro.sim.completion``) — e.g. ``["always", "bernoulli"]`` compares
    idealized rounds against mid-round dropout cell by cell; ``None``
    keeps each scenario's own completion process and the two-axis result
    shape.  ``rounds`` overrides every cell (otherwise scenario/task
    defaults apply) and ``eval_every`` defaults to evaluating only first +
    last round for short sweeps.  ``engine`` routes every cell through the
    device-resident engine (default) or the reference host loop
    (DESIGN.md §7); ``mesh_shape`` shards every cell over a ``(clients,)``
    or ``(clients, model)`` device mesh (DESIGN.md §7.2).
    """
    os.makedirs(out_dir, exist_ok=True)
    overrides = {k: v for k, v in dict(
        rounds=rounds, seed=seed, server_opt=server_opt,
        server_lr=server_lr, engine=engine, mesh_shape=mesh_shape,
        clients_axis=clients_axis, model_axis=model_axis).items()
        if v is not _UNSET}
    base = dataclasses.replace(base_spec or RunSpec(), **overrides)
    results = {}
    for sc_key in scenarios:
        sc = get_scenario(sc_key)
        algos = tuple(algorithms) if algorithms else sc.algorithms
        comps = tuple(completions) if completions else (None,)
        aggs = tuple(aggregations) if aggregations else (None,)
        for algo in algos:
            for comp in comps:
              for agg in aggs:
                cell = f"{sc.name}__{algo}"
                cell_key = (sc.name, algo)
                if completions:
                    cell = f"{cell}__{comp}"
                    cell_key = (sc.name, algo, comp)
                if aggregations:
                    cell = f"{cell}__{agg}"
                    cell_key = cell_key + (agg,)
                path = os.path.join(out_dir, f"{cell}.jsonl")
                ev = eval_every or max(1, (base.rounds or sc.rounds or 150)
                                       // 5)
                spec = dataclasses.replace(base, scenario=sc, strategy=algo,
                                           eval_every=ev, metrics_path=path)
                if comp is not None:
                    spec = dataclasses.replace(spec, completion=comp)
                if agg is not None:
                    spec = dataclasses.replace(spec, aggregation=agg)
                # mesh_shape is a plain tuple (JSON list round-trip), so the
                # spec artifact is always writable — no runtime-Mesh escape
                # hatch exists at the spec layer any more
                spec.save(os.path.join(out_dir, f"{cell}.spec.json"))
                res = run_scenario(spec, log_fn=lambda *_: None)
                results[cell_key] = res.final_metrics
                fm = res.final_metrics
                log_fn(f"sweep,{','.join(cell_key)},"
                       f"acc={fm.get('test_acc', float('nan')):.4f},"
                       f"loss={fm.get('test_loss', float('nan')):.4f},"
                       f"wall_s={fm['wall_s']:.1f} -> {path}")
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump({"|".join(k): m for k, m in results.items()}, f, indent=1)
    return results


def _parse_list(arg: str, universe: Sequence[str]) -> list:
    if arg == "all":
        return list(universe)
    return [x.strip() for x in arg.split(",") if x.strip()]


def _parse_mesh_shape(arg: str) -> tuple:
    """'4' -> (4,); '2,2' -> (2, 2).  Validation lives in RunSpec.resolved."""
    return tuple(int(x.strip()) for x in arg.split(",") if x.strip())


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="Scenario × strategy sweep (see repro/sim/scenario.py)")
    ap.add_argument("--scenarios", default="bernoulli,markov,diurnal",
                    help="comma-separated scenario keys, or 'all'")
    ap.add_argument("--algorithms", default=None,
                    help="comma-separated strategy names, or 'all' "
                         f"({','.join(ALGORITHMS)}); default: each "
                         "scenario's own grid")
    ap.add_argument("--completions", default=None,
                    help="comma-separated completion-process keys, or 'all' "
                         "— adds a mid-round-dropout axis to the grid "
                         "(default: each scenario's own completion process)")
    ap.add_argument("--aggregations", default=None,
                    help="comma-separated server-aggregation modes from "
                         "{sync,buffered}, or 'all' — adds a sync-vs-"
                         "FedBuff axis to the grid (DESIGN.md §7.4; "
                         "default: sync only)")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--out", default="experiments/sweep")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--server-opt", default="sgd")
    ap.add_argument("--eval-every", type=int, default=None)
    ap.add_argument("--engine", default="device", choices=["device", "host"],
                    help="device-resident scan engine (default) or the "
                         "reference host loop")
    ap.add_argument("--mesh-shape", default=None, metavar="C[,M]",
                    help="comma-separated device-mesh shape: '4' shards "
                         "clients over 4 devices, '2,2' also shards each "
                         "model over 2 (0 in a slot = fill with all "
                         "remaining devices; default: unsharded; "
                         "DESIGN.md §7.2)")
    ap.add_argument("--clients-axis", default="clients",
                    help="mesh axis name for the client shard (default "
                         "'clients')")
    ap.add_argument("--model-axis", default="model",
                    help="mesh axis name for the model shard (default "
                         "'model')")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name in list_scenarios():
            sc = SCENARIO_REGISTRY[name]
            print(f"{name:<16} avail={sc.availability:<16} "
                  f"budget={sc.budget:<9} task={sc.task:<12} "
                  f"{sc.description}")
        return

    scenarios = _parse_list(args.scenarios, list_scenarios())
    algorithms = (_parse_list(args.algorithms, ALGORITHMS) if args.algorithms
                  else None)
    completions = (_parse_list(args.completions, sorted(COMPLETION_REGISTRY))
                   if args.completions else None)
    aggregations = (_parse_list(args.aggregations, ("sync", "buffered"))
                    if args.aggregations else None)
    mesh_shape = (_parse_mesh_shape(args.mesh_shape)
                  if args.mesh_shape is not None else _UNSET)
    use_compile_cache()
    run_sweep(scenarios, algorithms, completions=completions,
              aggregations=aggregations,
              rounds=args.rounds, out_dir=args.out,
              seed=args.seed, server_opt=args.server_opt,
              eval_every=args.eval_every,
              engine=args.engine, mesh_shape=mesh_shape,
              clients_axis=args.clients_axis, model_axis=args.model_axis)


if __name__ == "__main__":
    main()
