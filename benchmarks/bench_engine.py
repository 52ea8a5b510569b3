"""Round-engine throughput: host loop vs device-resident vs vmapped cells,
plus the client-sharded N-scaling column.

Measures steady-state rounds/sec (first round / first chunk excluded — that
is where XLA compiles) for the three execution paths of one
(scenario × algorithm) cell on ``synthetic11``:

* ``host``           — the reference Python loop (``sim/runner.py``,
                       ``engine="host"``): per-round host↔device syncs.
* ``device``         — the chunked ``lax.scan`` engine (``sim/engine.py``):
                       one sync per chunk.
* ``device_dropout`` — the same engine with a mid-round completion process
                       (``completion="bernoulli"``, q=0.8): guards the
                       dropout path's throughput (the extra per-round cost
                       is one bernoulli draw + a mask multiply, so it must
                       stay close to ``device``).
* ``device_buffered``— the buffered-async engine (``sim/engine_async.py``,
                       ``aggregation="buffered"``): the same compiled scan
                       plus the pending-arrival pool (insert + 3-pass sort
                       + flush per server step); ``buffered_over_sync_ratio``
                       guards how much of the sync engine's throughput the
                       pool bookkeeping costs.
* ``vmapped8``       — 8 cells (seeds 0..7) in one vmapped program
                       (``run_cells_vmapped``); rounds/sec counts all cells.

``--nscale`` adds the client-scaling column in two modes per N:

* ``staged`` (N ≤ 1e5) — client data materialized and staged on device
  (the legacy cells, kept for baseline continuity);
* ``synth`` (N ≥ 1e5) — on-demand keyed cohort synthesis
  (``data.SynthTask``): nothing O(N) is resident, which is what lets the
  column reach N = 1e6 on both engines and N = 1e7 on the sharded engine
  (``--n-smoke-1e7``, a few rounds, existence proof not throughput).

The N = 1e5 cells additionally run a ``sharded2d`` column: the same round
on a two-axis ``(clients, model)`` mesh (``make_fed_mesh((0, 2))``), with
each cohort client's parameters sharded over the ``model`` axis.  The
per-cell ``mesh2d_over_1d_ratio`` (2-D rounds/s over 1-D sharded
rounds/s) is gated in CI — on CPU the model axis buys no FLOPs, so the
floor only bounds the overhead of the gather/slice/psum plumbing.

Each engine cell also records the scale-accounting columns —
``n_staged_bytes`` (resident client-data bytes; 0 for synth),
``staged_bytes_per_client``, and ``selection_comm_bytes_per_round`` (the
sharded engine's analytic per-shard selection traffic under the packed
uint32 mask wire format).  Run the sharded cells with all visible devices
— ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU.  The
unsharded cell is attempted and recorded as ``oom`` if the single-device
path cannot stage/run it.

Writes the JSON consumed by ``tools/check_bench_regression.py`` in CI
(fails the build on a >30% rounds/sec regression vs the committed baseline
in ``experiments/bench/BENCH_engine.json``, or if the device engine loses
its speedup over the host loop, or if the sharded N=100k cell stops
completing).

    PYTHONPATH=src python benchmarks/bench_engine.py --quick
    PYTHONPATH=src python benchmarks/bench_engine.py   # refresh the baseline
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python benchmarks/bench_engine.py --quick \\
        --nscale-only --out experiments/bench/BENCH_engine_nscale.json
"""
from __future__ import annotations

import argparse
import functools
import json
import platform
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.core.fedstep import make_fed_round
from repro.core.strategies import make_strategy
from repro.data.pipeline import stage_client_arrays
from repro.data.synthetic import SynthTask, make_synthetic_client_arrays
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_client_mesh, make_fed_mesh
from repro.sharding.rules import model_specs
from repro.models import softmax_reg
from repro.models.softmax_reg import SoftmaxRegConfig
from repro.optim import make_optimizer
from repro.sim import RunSpec, run_cells_vmapped, run_scenario
from repro.sim.budgets import make_budget
from repro.sim.engine import DeviceEngine
from repro.sim.engine_sharded import ShardedEngine
from repro.sim.processes import make_process


def _silent(*args, **kwargs):
    pass


def bench_host(scenario: str, algo: str, rounds: int, seed: int) -> dict:
    spec = RunSpec(scenario=scenario, strategy=algo, rounds=rounds,
                   seed=seed, eval_every=rounds, engine="host")
    res = run_scenario(spec, log_fn=_silent)
    return dict(rounds=rounds,
                wall_s=round(res.final_metrics["wall_s"], 4),
                rounds_per_s=round(res.final_metrics["steady_rounds_per_s"], 2))


def bench_device(scenario: str, algo: str, rounds: int, seed: int,
                 chunk_size: int, completion=None,
                 completion_kwargs=None) -> dict:
    spec = RunSpec(scenario=scenario, strategy=algo, rounds=rounds,
                   seed=seed, eval_every=rounds, chunk_size=chunk_size,
                   engine="device", completion=completion,
                   completion_kwargs=completion_kwargs or {})
    res = run_scenario(spec, log_fn=_silent)
    return dict(rounds=rounds, chunk_size=chunk_size,
                wall_s=round(res.final_metrics["wall_s"], 4),
                rounds_per_s=round(res.final_metrics["steady_rounds_per_s"], 2))


def bench_buffered(scenario: str, algo: str, rounds: int, seed: int,
                   chunk_size: int) -> dict:
    spec = RunSpec(scenario=scenario, strategy=algo, rounds=rounds,
                   seed=seed, eval_every=rounds, chunk_size=chunk_size,
                   engine="device", aggregation="buffered")
    res = run_scenario(spec, log_fn=_silent)
    return dict(rounds=rounds, chunk_size=chunk_size,
                wall_s=round(res.final_metrics["wall_s"], 4),
                rounds_per_s=round(res.final_metrics["steady_rounds_per_s"], 2))


def bench_vmapped(scenario: str, algo: str, rounds: int, cells: int,
                  chunk_size: int) -> dict:
    res = run_cells_vmapped(scenario, algo, seeds=list(range(cells)),
                            rounds=rounds, chunk_size=chunk_size)
    return dict(rounds=rounds, cells=cells, chunk_size=chunk_size,
                wall_s=round(res["wall_s"], 4),
                rounds_per_s=round(res["steady_rounds_per_s"], 2))


def _build_nscale_engine(n_clients: int, mesh, *, dim: int = 32,
                         n_classes: int = 10, samples: int = 64,
                         k: int = 10, seed: int = 0, synth: bool = False,
                         topk_impl: str = "stream", model_axis=None):
    """One synthetic N-scaling cell (vectorized data, no per-client loop).

    ``synth=True`` hands the engine a :class:`repro.data.SynthTask` instead
    of staged arrays: cohort batches are synthesized on demand inside the
    compiled loop, so device-resident client data is 0 bytes regardless of
    N — the path that makes the 1e6/1e7 cells possible at all.

    ``model_axis`` (with a 2-D mesh naming it) additionally shards each
    cohort client's parameters over that axis — the two-axis engine path.
    """
    if synth:
        staged = SynthTask(n_clients=n_clients, dim=dim, n_classes=n_classes,
                           samples_per_client=samples, seed=seed)
    else:
        arrays, counts = make_synthetic_client_arrays(
            n_clients, dim=dim, n_classes=n_classes,
            samples_per_client=samples, seed=seed)
        staged = stage_client_arrays(arrays, counts, mesh=mesh)
    cfg = SoftmaxRegConfig(dim=dim, n_classes=n_classes)
    loss = functools.partial(softmax_reg.loss_fn, cfg)
    opt = make_optimizer("sgd", lr=1.0)
    common = dict(
        avail_model=make_process("bernoulli", n_clients, q=0.3),
        budget=make_budget("constant", k=k),
        strategy=make_strategy("f3ast", n_clients,
                               np.full(n_clients, 1.0 / n_clients, np.float32),
                               clients_per_round=k),   # init calibrates K/N
        init_params=functools.partial(softmax_reg.init_params, cfg),
        opt=opt, client_lr=0.05, local_steps=5, local_batch=20)
    if mesh is None:
        engine = DeviceEngine(
            staged=staged, fed_round=make_fed_round(loss, opt), **common)
    else:
        fkw, ekw = {}, {}
        if model_axis is not None and model_axis in mesh.axis_names:
            p_shapes = jax.eval_shape(common["init_params"],
                                      jax.random.PRNGKey(0))
            fkw = dict(model_axis=model_axis,
                       param_specs=model_specs(p_shapes, mesh,
                                               model_axis=model_axis))
            ekw = dict(model_axis=model_axis)
        engine = ShardedEngine(
            mesh=mesh, axis="clients", staged=staged, n_clients=n_clients,
            topk_impl=topk_impl,
            fed_round=make_fed_round(loss, opt, cohort_axis="clients",
                                     cohort_slots=k, **fkw),
            **ekw, **common)
    return engine


def _time_engine(engine, rounds: int, chunk: int) -> dict:
    """Steady-state rounds/s of engine.chunk (first chunk = compile, excluded)."""
    carry = engine.init_carry(jax.random.PRNGKey(0))
    t0 = 0
    t_first = None
    t_start = time.time()
    while t0 < rounds:
        t1 = min(t0 + chunk, rounds)
        carry, out = engine.chunk(carry, jnp.arange(t0, t1, dtype=jnp.int32))
        jax.block_until_ready(out.train_loss)
        if t_first is None:
            t_first = time.time()
        t0 = t1
    t_end = time.time()
    steady = rounds - min(chunk, rounds)
    rps = steady / (t_end - t_first) if steady and t_end > t_first else 0.0
    return dict(rounds=rounds, chunk_size=chunk,
                wall_s=round(t_end - t_start, 4),
                rounds_per_s=round(rps, 2))


def _is_resource_exhausted(e: BaseException) -> bool:
    """A genuine out-of-memory: Python's own, or XLA's RESOURCE_EXHAUSTED
    status (device or host allocator).  Every other runtime error —
    a compile refusal from XLA or Mosaic among them — is not one."""
    return isinstance(e, MemoryError) or "RESOURCE_EXHAUSTED" in str(e)


def bench_nscale(cells_spec, rounds: int, chunk: int) -> dict:
    """Unsharded vs client-sharded engine across client counts N.

    ``cells_spec``: iterable of (n_clients, mode, engines, cell_rounds)
    with mode "staged" | "synth"; ``cell_rounds=None`` uses ``rounds``.
    The ``sharded2d`` engine runs the same cell on a two-axis
    ``(clients, model)`` mesh (skipped below 2 devices); its throughput
    relative to the 1-D sharded cell is ``mesh2d_over_1d_ratio``.
    """
    mesh = make_client_mesh(axis_name="clients")
    mesh2d = (make_fed_mesh((0, 2)) if jax.device_count() >= 2 else None)
    out = dict(devices=jax.device_count(),
               task=dict(dim=32, n_classes=10, samples_per_client=64, k=10),
               cells=[])
    for n, mode, engines, cell_rounds in cells_spec:
        r = cell_rounds or rounds
        cell = dict(n_clients=n, mode=mode)
        for label, m in (("device", None), ("sharded", mesh),
                         ("sharded2d", mesh2d)):
            if label not in engines:
                continue
            if label == "sharded2d" and m is None:
                print(f"  N={n:>8d} {mode:>6s} {label:>8s} skipped "
                      f"(needs >= 2 devices)")
                continue
            print(f"  N={n:>8d} {mode:>6s} {label:>8s} ...", end=" ",
                  flush=True)
            engine = None
            try:
                engine = _build_nscale_engine(
                    n, m, synth=(mode == "synth"),
                    model_axis="model" if label == "sharded2d" else None)
                cell[label] = _time_engine(engine, r, chunk)
                cell[label]["n_staged_bytes"] = engine.n_staged_bytes
                cell[label]["staged_bytes_per_client"] = round(
                    engine.n_staged_bytes / n, 2)
                cell[label]["selection_comm_bytes_per_round"] = (
                    engine.selection_comm_bytes_per_round)
                print(f"{cell[label]['rounds_per_s']:.1f} rounds/s")
            except (MemoryError, jax.errors.JaxRuntimeError) as e:
                if not _is_resource_exhausted(e):
                    raise                  # a compile refusal is not an OOM
                cell[label] = dict(status="oom", error=str(e)[:200])
                print("OOM")
            del engine   # release staged arrays before the next cell
        if "rounds_per_s" in cell.get("device", {}) \
                and "rounds_per_s" in cell.get("sharded", {}) \
                and cell["device"]["rounds_per_s"] > 0:
            cell["speedup_sharded_over_device"] = round(
                cell["sharded"]["rounds_per_s"]
                / cell["device"]["rounds_per_s"], 2)
        if "rounds_per_s" in cell.get("sharded2d", {}) \
                and "rounds_per_s" in cell.get("sharded", {}) \
                and cell["sharded"]["rounds_per_s"] > 0:
            cell["mesh2d_over_1d_ratio"] = round(
                cell["sharded2d"]["rounds_per_s"]
                / cell["sharded"]["rounds_per_s"], 3)
        out["cells"].append(cell)
    ratios = [c["mesh2d_over_1d_ratio"] for c in out["cells"]
              if "mesh2d_over_1d_ratio" in c]
    if ratios:
        # worst cell gates CI: the 2-D mesh must not cost more than the
        # floor relative to pure client sharding on the same devices
        out["mesh2d_over_1d_ratio"] = min(ratios)
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        description="host vs device-resident vs vmapped round-engine bench")
    ap.add_argument("--scenario", default="scarce")
    ap.add_argument("--algo", default="f3ast")
    ap.add_argument("--quick", action="store_true",
                    help="short CI-sized run (fewer rounds)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cells", type=int, default=8,
                    help="vmapped cell count (seeds 0..cells-1)")
    ap.add_argument("--nscale", action="store_true",
                    help="also run the client-scaling column (unsharded vs "
                         "sharded engine up to --n-max clients)")
    ap.add_argument("--nscale-only", action="store_true",
                    help="run only the client-scaling column (use with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--n-max", type=int, default=1_000_000,
                    help="largest client count in the N-scaling column")
    ap.add_argument("--n-smoke-1e7", action="store_true",
                    help="add a sharded-only N=1e7 on-demand-synthesis "
                         "smoke cell (a few rounds; proves the round fits, "
                         "not a throughput claim)")
    ap.add_argument("--out", default="experiments/bench/BENCH_engine.json",
                    help="output path (the default overwrites the committed "
                         "CI baseline — pass an explicit path to compare)")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.quick:
        host_rounds, dev_rounds, chunk = 80, 240, 40
        nscale_rounds, nscale_chunk = 24, 8
    else:
        host_rounds, dev_rounds, chunk = 200, 600, 60
        nscale_rounds, nscale_chunk = 48, 12

    result = dict(
        benchmark="engine",
        scenario=args.scenario, algorithm=args.algo, task="synthetic11",
        quick=bool(args.quick),
        platform=dict(backend=jax.default_backend(),
                      device_count=jax.device_count(),
                      jax=jax.__version__,
                      python=platform.python_version(),
                      machine=platform.machine()),
    )
    if args.nscale or args.nscale_only:
        both = ("device", "sharded")
        with2d = both + ("sharded2d",)     # 2-D mesh column lives at N=1e5
        cells_spec = [(n, "staged", with2d if n == 100_000 else both, None)
                      for n in (1_000, 10_000, 100_000) if n <= args.n_max]
        cells_spec += [(n, "synth", with2d if n == 100_000 else both, None)
                       for n in (100_000, 1_000_000) if n <= args.n_max]
        if args.n_smoke_1e7:
            # chunk + 2 rounds: one compile chunk plus a measurable tail
            cells_spec.append((10_000_000, "synth", ("sharded",),
                               nscale_chunk + 2))
        print(f"benching N-scaling column (unsharded vs sharded, "
              f"{jax.device_count()} devices, {nscale_rounds} rounds) ...")
        result["nscale"] = bench_nscale(cells_spec, nscale_rounds,
                                        nscale_chunk)
    if args.nscale_only:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        print(f"wrote {args.out}")
        return result

    print(f"benching host loop        ({host_rounds} rounds) ...")
    result["host"] = bench_host(args.scenario, args.algo, host_rounds,
                                args.seed)
    print(f"  -> {result['host']['rounds_per_s']:.1f} rounds/s")
    print(f"benching device engine    ({dev_rounds} rounds, "
          f"chunk={chunk}) ...")
    result["device"] = bench_device(args.scenario, args.algo, dev_rounds,
                                    args.seed, chunk)
    print(f"  -> {result['device']['rounds_per_s']:.1f} rounds/s")
    print(f"benching device + dropout ({dev_rounds} rounds, "
          f"chunk={chunk}) ...")
    result["device_dropout"] = bench_device(
        args.scenario, args.algo, dev_rounds, args.seed, chunk,
        completion="bernoulli", completion_kwargs={"q": 0.8})
    print(f"  -> {result['device_dropout']['rounds_per_s']:.1f} rounds/s")
    print(f"benching device buffered  ({dev_rounds} rounds, "
          f"chunk={chunk}) ...")
    result["device_buffered"] = bench_buffered(
        args.scenario, args.algo, dev_rounds, args.seed, chunk)
    print(f"  -> {result['device_buffered']['rounds_per_s']:.1f} rounds/s")
    print(f"benching vmapped x{args.cells}       ({dev_rounds} rounds) ...")
    result[f"vmapped{args.cells}"] = bench_vmapped(
        args.scenario, args.algo, dev_rounds, args.cells, chunk)
    print(f"  -> {result[f'vmapped{args.cells}']['rounds_per_s']:.1f} "
          f"cell-rounds/s")

    host_rps = result["host"]["rounds_per_s"]
    result["speedup_device_over_host"] = round(
        result["device"]["rounds_per_s"] / host_rps, 2)
    result["speedup_vmapped_over_host"] = round(
        result[f"vmapped{args.cells}"]["rounds_per_s"] / host_rps, 2)
    # the dropout path folds one extra bernoulli + mask multiply into the
    # compiled round — it must stay close to the plain device engine
    result["dropout_over_device_ratio"] = round(
        result["device_dropout"]["rounds_per_s"]
        / result["device"]["rounds_per_s"], 3)
    # the buffered engine adds pool insert/sort/flush per server step on
    # top of the same compiled round — bound how much throughput that costs
    result["buffered_over_sync_ratio"] = round(
        result["device_buffered"]["rounds_per_s"]
        / result["device"]["rounds_per_s"], 3)

    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(f"device engine speedup over host: "
          f"{result['speedup_device_over_host']:.2f}x")
    print(f"vmapped x{args.cells} speedup over host: "
          f"{result['speedup_vmapped_over_host']:.2f}x")
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()
