"""Federated training driver (CLI front-end).

Runs the full F3AST system end-to-end: availability process -> selection
strategy (F3AST / FedAvg / PoC / any ``register_strategy`` plug-in) ->
cohort batch assembly -> jitted federated round (local SGD + unbiased
aggregation + server optimizer) -> metrics / checkpoints.  Works for the
paper's tasks and for reduced assigned-arch configs on CPU; the same round
program lowers to the production mesh.

The experiment loop itself lives in :mod:`repro.sim.runner`; this module
parses the CLI straight into one frozen :class:`repro.sim.spec.RunSpec`
(JSON-serializable — ``--save-spec``/``--spec`` make any run reproducible
from a single artifact).  Scenarios (an availability process × K_t budget ×
task bound together — DESIGN.md §7) are the preferred spelling:

  python -m repro.launch.train --scenario diurnal --algo f3ast --rounds 200
  python -m repro.launch.train --task synthetic11 --algo f3ast --rounds 200
  python -m repro.launch.train --task shakespeare --algo fedavg \
      --availability homedevices --server-opt adam
  python -m repro.launch.train --spec experiments/run.spec.json
  python -m repro.launch.train --arch llama3.2-1b --smoke --rounds 5

For grids over scenarios × strategies use ``python -m repro.sim.sweep``.
"""
from __future__ import annotations

import argparse
import json
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, PAPER_TASKS, get_arch
from ..core import make_availability
from ..core.fedstep import make_fed_round
from ..core.strategies import STRATEGY_ALIASES, list_strategies, make_strategy
from ..models import get_model_api
from ..optim import make_optimizer
from ..sim.completion import COMPLETION_REGISTRY
from ..sim.runner import TrainResult, run_scenario
from ..sim.scenario import Scenario, list_scenarios
from ..sim.spec import RunSpec
from .compile_cache import use_compile_cache

__all__ = ["TrainResult", "run_federated", "run_arch_smoke", "main"]


def run_federated(task_id: str = "synthetic11", algo_name: str = "f3ast",
                  availability: str = "homedevices", rounds: Optional[int] = None,
                  server_opt: str = "sgd", server_lr: Optional[float] = None,
                  clients_per_round: Optional[int] = None,
                  k_jitter: int = 0, beta: Optional[float] = None,
                  seed: int = 0, eval_every: int = 10,
                  ckpt_dir: Optional[str] = None, prox_mu: float = 0.0,
                  log_fn: Callable = print, positively_correlated: bool = False,
                  metrics_path: Optional[str] = None,
                  engine: str = "device", mesh_shape=None,
                  clients_axis: str = "clients",
                  model_axis: str = "model") -> TrainResult:
    """Availability-string front-end: wraps the arguments into an ad-hoc
    :class:`Scenario` + :class:`RunSpec` and runs it through
    :func:`repro.sim.runner.run_spec`.
    """
    from ..sim.runner import _legacy_server_lr
    sc = Scenario(name=availability, availability=availability,
                  budget="jittered" if k_jitter else "constant",
                  budget_kwargs={"jitter": k_jitter} if k_jitter else {},
                  task=task_id)
    spec = RunSpec(scenario=sc, strategy=algo_name, rounds=rounds,
                   server_opt=server_opt,
                   server_lr=_legacy_server_lr(algo_name, server_lr),
                   clients_per_round=clients_per_round, beta=beta, seed=seed,
                   eval_every=eval_every, ckpt_dir=ckpt_dir, prox_mu=prox_mu,
                   positively_correlated=positively_correlated,
                   metrics_path=metrics_path, engine=engine,
                   mesh_shape=mesh_shape, clients_axis=clients_axis,
                   model_axis=model_axis)
    return run_scenario(spec, log_fn=log_fn)


def run_arch_smoke(arch_id: str, rounds: int = 3, seed: int = 0,
                   log_fn: Callable = print):
    """Few federated rounds of the REDUCED assigned-arch model on CPU."""
    arch = get_arch(arch_id)
    cfg = arch.smoke_model
    api = get_model_api(cfg)
    key = jax.random.PRNGKey(seed)
    params = api.init_params(key)
    opt = make_optimizer("adam", lr=1e-3)
    opt_state = opt.init(params)
    fed_round = jax.jit(make_fed_round(api.loss_fn, opt, mode="parallel"))

    K, E, B, S = 4, 2, 2, 64
    N = 16
    p = np.full(N, 1.0 / N, np.float32)
    strategy = make_strategy("f3ast", N, p, clients_per_round=K)
    algo_state = strategy.init(N)
    avail_proc = make_availability("scarce", N, q=0.5)

    losses = []
    for t in range(rounds):
        key, k1, k2, kb, kb_aux = jax.random.split(key, 5)
        avail = avail_proc.sample(k1, t)
        sel, w_full, algo_state = strategy.select(algo_state, k2, avail,
                                                  jnp.asarray(K), None)
        sel_ids = np.flatnonzero(np.asarray(sel))
        ids = (list(sel_ids) + [int(sel_ids[0])] * K)[:K]
        batch = {"tokens": jax.random.randint(kb, (K, E, B, S), 0, cfg.vocab)}
        if cfg.family == "vlm":
            batch["patch_embeds"] = jax.random.normal(
                kb_aux, (K, E, B, cfg.n_patches, cfg.vit_dim), cfg.np_dtype)
        if cfg.family == "audio":
            batch["frames"] = jax.random.normal(  # reprolint: disable=R1 -- vlm/audio branches are mutually exclusive; kb_aux is consumed once per run
                kb_aux, (K, E, B, cfg.enc_seq, cfg.d_model), cfg.np_dtype)
        w = jnp.asarray(np.asarray(w_full)[ids])
        params, opt_state, m = fed_round(params, opt_state, batch, w,
                                         jnp.asarray(1e-2, jnp.float32))
        losses.append(float(m.loss))
        log_fn(f"[{arch_id}-smoke] round {t} loss={losses[-1]:.4f}")
    assert all(np.isfinite(losses)), losses
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default=None, choices=list(PAPER_TASKS))
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--scenario", default=None, choices=list_scenarios(),
                    help="registered scenario key (overrides --availability; "
                         "see python -m repro.sim.sweep --list)")
    ap.add_argument("--algo", default="f3ast",
                    choices=sorted(list_strategies()
                                   + list(STRATEGY_ALIASES)),
                    help="registered selection strategy (or alias)")
    ap.add_argument("--availability", default="homedevices")
    ap.add_argument("--completion", default=None,
                    choices=sorted(COMPLETION_REGISTRY),
                    help="mid-round completion process (selected ≠ "
                         "completed; default: the scenario's own, usually "
                         "'always')")
    ap.add_argument("--completion-kwargs", default=None, metavar="JSON",
                    help="JSON dict of completion-process parameters, e.g. "
                         "'{\"q\": 0.7}'")
    ap.add_argument("--aggregation", default="sync",
                    choices=["sync", "buffered"],
                    help="server semantics: round-synchronous (default) or "
                         "FedBuff-style buffered-asynchronous aggregation "
                         "(DESIGN.md §7.4)")
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="buffered aggregation: arrivals aggregated per "
                         "server step (default: half the per-round budget)")
    ap.add_argument("--staleness-power", type=float, default=0.5,
                    help="buffered aggregation: staleness-discount exponent "
                         "(weight ∝ 1/(1+staleness)^power)")
    ap.add_argument("--staleness-discount", default="polynomial",
                    help="buffered aggregation: discount family from the "
                         "STALENESS_DISCOUNTS registry (polynomial, "
                         "exponential, or a registered plug-in)")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--server-opt", default=None)
    ap.add_argument("--clients-per-round", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--metrics-jsonl", default=None,
                    help="stream per-round metrics to this JSONL file")
    ap.add_argument("--prox-mu", type=float, default=0.0,
                    help="FedProx proximal coefficient (0 = plain local SGD)")
    ap.add_argument("--engine", default="device", choices=["device", "host"],
                    help="device-resident scan engine (default) or the "
                         "reference host loop (DESIGN.md §7.1)")
    ap.add_argument("--select-impl", default="xla",
                    choices=["xla", "pallas"],
                    help="top-k cut implementation: reference XLA "
                         "(default) or the fused Pallas selection kernel "
                         "(bit-identical masks/rates; docs/kernels.md)")
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
    ap.add_argument("--spec", default=None, metavar="PATH",
                    help="load a RunSpec JSON and run it (the other run "
                         "flags are ignored)")
    ap.add_argument("--save-spec", default=None, metavar="PATH",
                    help="write the assembled RunSpec JSON before running "
                         "(reproduce later with --spec)")
    args = ap.parse_args()
    use_compile_cache()

    if args.arch:
        run_arch_smoke(args.arch, rounds=args.rounds or 3, seed=args.seed)
        return
    if args.spec:
        spec = RunSpec.load(args.spec)
    else:
        scenario = args.scenario if args.scenario else Scenario(
            name=args.availability, availability=args.availability,
            task=args.task or "synthetic11")
        # alias resolution (fedadam -> fedavg + adam server) and server-lr
        # defaulting happen inside the strategy registry at run time
        spec = RunSpec(scenario=scenario, strategy=args.algo,
                       rounds=args.rounds,
                       completion=args.completion,
                       completion_kwargs=(json.loads(args.completion_kwargs)
                                          if args.completion_kwargs else {}),
                       server_opt=args.server_opt or "sgd",
                       clients_per_round=args.clients_per_round,
                       seed=args.seed, ckpt_dir=args.ckpt_dir,
                       prox_mu=args.prox_mu, engine=args.engine,
                       select_impl=args.select_impl,
                       mesh_shape=(tuple(int(x) for x in
                                         args.mesh_shape.split(","))
                                   if args.mesh_shape else None),
                       clients_axis=args.clients_axis,
                       model_axis=args.model_axis,
                       aggregation=args.aggregation,
                       buffer_size=args.buffer_size,
                       staleness_power=args.staleness_power,
                       staleness_discount=args.staleness_discount,
                       metrics_path=args.metrics_jsonl)
    if args.save_spec:
        spec.save(args.save_spec)
        print(f"wrote {args.save_spec}")
    res = run_scenario(spec)
    print(json.dumps(res.final_metrics, indent=1))


if __name__ == "__main__":
    main()
