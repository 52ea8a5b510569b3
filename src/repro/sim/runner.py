"""Scenario executor: one (scenario × strategy) cell end-to-end.

This is the execution front-end behind both ``repro.launch.train`` and
``repro.sim.sweep``.  The canonical entry point takes a single frozen
:class:`repro.sim.spec.RunSpec`:

    spec = RunSpec(scenario="diurnal", strategy="f3ast", rounds=200)
    result = run_scenario(spec)

The old kwarg spelling ``run_scenario(scenario, algo_name, rounds=...,
...)`` is kept as a thin deprecation shim for one PR — it builds the
equivalent RunSpec and emits a ``DeprecationWarning``.

Three engines implement the same cell semantics (DESIGN.md §7), selected
by ``spec.engine`` / ``spec.mesh_shape``:

* ``engine="device"`` (default) — the device-resident chunked-``lax.scan``
  engine in :mod:`repro.sim.engine`; with ``mesh_shape`` set, the
  client-sharded variant (:mod:`repro.sim.engine_sharded`), which with a
  2-D ``(c, m)`` shape also shards each cohort client's parameters over
  the ``model`` axis.
* ``engine="host"`` — the reference Python loop below: availability step →
  strategy ``select`` (completion-aware, DESIGN.md §7.3) → static-shape
  cohort batch → jitted federated round → per-round metrics.  Kept as the
  readable, debuggable ground truth the engines are parity-tested
  against, and the only path for host-only strategies (PoC's fresh
  per-client losses).

All paths resolve the strategy through ONE registry call
(``repro.core.strategies.resolve_strategy``) before dispatch, so aliases
like ``fedadam`` and unknown-name errors behave identically on every
engine.  Both execution paths split the per-round PRNG key identically
(avail / select / budget / batch) and draw minibatch indices from the same
``jax.random.randint``, so selection masks, rates, and batches match
bit-for-bit for the same seed (``tests/test_engine.py``).

Per-round metrics stream to JSONL when ``spec.metrics_path`` is given: one
self-describing record per round (scenario, algorithm, K_t, availability
and selection counts, train loss) plus test metrics on eval rounds,
flushed as written so long sweeps are tail-able and crash-safe.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
import warnings
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import save_checkpoint
from ..configs import PAPER_TASKS
from ..core.fedstep import make_fed_round
from ..core.strategies import (SelectCtx, get_strategy_entry, make_strategy,
                               select_path, strategy_rates)
from ..data import CohortSampler, FederatedData
from ..data.synthetic import (make_char_lm_federated, make_synthetic_federated,
                              make_vision_federated)
from ..models import resnet, rnn, softmax_reg
from ..optim import make_optimizer
from ..core.keys import COMPLETION as KEY_FOLD
from .scenario import Scenario, get_scenario
from .spec import RunSpec


@dataclasses.dataclass
class TrainResult:
    history: list            # per-eval-round dicts
    final_metrics: dict
    rates: np.ndarray        # learned r(T) (NaN for rate-free strategies)
    empirical_rates: np.ndarray   # time-average of the *selection* masks
    sel_history: Optional[np.ndarray] = None   # (T, N) bool selection masks
    comp_history: Optional[np.ndarray] = None  # (T, N) bool completed masks
    #   (== sel_history under completion="always"; the r_k EMA tracks these;
    #   under aggregation="buffered" it marks the clients aggregated at t)
    async_history: Optional[dict] = None       # buffered runs only: per-step
    #   buf_ids/buf_valid/buf_staleness/buf_weights (T, M) plus n_buffered /
    #   mean_staleness / n_overflow (T,) — see sim.engine_async


def build_task(task_id: str, seed: int, **task_kwargs):
    """Resolve a PAPER_TASKS key into (task, data, init, loss, acc).

    ``task_kwargs`` are forwarded to the federated data maker — e.g.
    ``alpha``/``beta`` select the Synthetic(α, β) heterogeneity level.
    """
    task = PAPER_TASKS[task_id]
    if task_id == "synthetic11":
        # §D.1: "The samples are split evenly among 100 clients."
        kw = dict(samples_per_client=100)
        kw.update(task_kwargs)
        clients = make_synthetic_federated(n_clients=task.n_clients,
                                           seed=seed, **kw)
        cfg = task.model_cfg
        init = functools.partial(softmax_reg.init_params, cfg)
        loss = functools.partial(softmax_reg.loss_fn, cfg)
        acc = functools.partial(softmax_reg.accuracy, cfg)
    elif task_id == "shakespeare":
        clients = make_char_lm_federated(n_clients=task.n_clients, seed=seed,
                                         **task_kwargs)
        cfg = task.model_cfg
        init = functools.partial(rnn.init_params, cfg)
        loss = functools.partial(rnn.loss_fn, cfg)
        acc = functools.partial(rnn.accuracy, cfg)
    elif task_id == "cifar":
        clients = make_vision_federated(n_clients=task.n_clients, seed=seed,
                                        **task_kwargs)
        cfg = task.model_cfg
        _, strides = resnet.init_params(cfg, jax.random.PRNGKey(seed))

        def init(key):
            return resnet.init_params(cfg, key)[0]

        def acc(p, b):
            return resnet.accuracy(cfg, p, strides, b)

        loss = resnet.make_loss_fn(cfg, strides)
    else:
        raise KeyError(task_id)
    return task, FederatedData(clients), init, loss, acc


# Kwargs the deprecated run_scenario(scenario, algo, **kwargs) spelling
# accepted, mapped onto their RunSpec fields.  "mesh" (a scalar shard
# count) predates RunSpec.mesh_shape and is rewritten to a 1-D shape.
_LEGACY_FIELDS = ("rounds", "server_opt", "clients_per_round", "beta",
                  "seed", "eval_every", "ckpt_dir", "prox_mu",
                  "positively_correlated", "metrics_path", "engine",
                  "chunk_size", "mesh", "mesh_shape", "clients_axis",
                  "model_axis", "strategy_kwargs")


def _legacy_server_lr(algo_name: str, server_lr) -> Optional[float]:
    """Old-signature server_lr semantics: the default was 1.0, and only the
    alias rewrite (fedadam) treated that value as "unset" (-> 1e-2).  A
    plain adam/yogi run with the old default therefore really trained at
    lr 1.0 — keep that, rather than silently re-defaulting to 1e-2."""
    from ..core.strategies import STRATEGY_ALIASES
    if server_lr is None:
        server_lr = 1.0
    if server_lr == 1.0 and str(algo_name).lower() in STRATEGY_ALIASES:
        return None            # let the alias fill its own default
    return server_lr


def _legacy_spec(scenario, algo_name, kwargs) -> RunSpec:
    warnings.warn(
        "run_scenario(scenario, algo_name, **kwargs) is deprecated; build "
        "a repro.sim.RunSpec and call run_scenario(spec)",
        DeprecationWarning, stacklevel=3)
    unknown = set(kwargs) - set(_LEGACY_FIELDS) - {"server_lr"}
    if unknown:
        raise TypeError(f"run_scenario() got unexpected keyword arguments "
                        f"{sorted(unknown)}")
    algo_name = algo_name or "f3ast"
    server_lr = _legacy_server_lr(algo_name, kwargs.pop("server_lr", None))
    fields = {k: v for k, v in kwargs.items() if k in _LEGACY_FIELDS}
    if "mesh" in fields:
        mesh = fields.pop("mesh")
        if "mesh_shape" in fields:
            raise TypeError("pass either mesh= (deprecated scalar) or "
                            "mesh_shape=, not both")
        if mesh is not None:
            if isinstance(mesh, bool) or not isinstance(mesh, (int, np.integer)):
                raise TypeError(
                    f"legacy mesh= takes an int shard count (got "
                    f"{type(mesh).__name__}); prebuilt Mesh objects go "
                    f"through sim.engine.build_engine, tuples through "
                    f"mesh_shape=")
            fields["mesh_shape"] = (max(int(mesh), 0),)
    return RunSpec(scenario=scenario, strategy=algo_name,
                   server_lr=server_lr, **fields)


def run_scenario(spec: Union[RunSpec, str, Scenario] = None,
                 algo_name: Optional[str] = None, *,
                 log_fn: Callable = print, **kwargs) -> TrainResult:
    """Run one (scenario × strategy) cell and return its TrainResult.

    Canonical form: ``run_scenario(spec)`` with a :class:`RunSpec`
    (``log_fn`` is the only runtime-side argument — it is not
    configuration, so it is not part of the spec).  The deprecated
    ``run_scenario(scenario, algo_name, **kwargs)`` form still works for
    one PR and forwards here.
    """
    if spec is None and "scenario" in kwargs:
        spec = kwargs.pop("scenario")   # old first parameter, by keyword
    if spec is None:
        raise TypeError("run_scenario() needs a RunSpec (or the deprecated "
                        "scenario key/Scenario first argument)")
    if not isinstance(spec, RunSpec):
        spec = _legacy_spec(spec, algo_name, kwargs)
    elif algo_name is not None or kwargs:
        raise TypeError("with a RunSpec, pass overrides via spec.replace("
                        "...) instead of extra arguments")
    return run_spec(spec, log_fn=log_fn)


def run_spec(spec: RunSpec, *, log_fn: Callable = print) -> TrainResult:
    """Execute a :class:`RunSpec` on the engine it names.

    ``spec.resolved()`` validates up front — unknown strategy/scenario
    keys raise ``KeyError`` (listing the registered names) before anything
    compiles, and strategy aliases resolve once for every engine.
    Host-only strategies (``needs_losses``/``host_only`` registry flags)
    fall back from the device engines to the host loop with an explicit
    warning; the engine that actually ran is reported in
    ``final_metrics["engine"]``.
    """
    rs = spec.resolved()
    algo_label = spec.strategy       # requested name (pre-alias), for logs
    sc = get_scenario(rs.scenario)
    entry = get_strategy_entry(rs.strategy)
    if rs.aggregation == "buffered":
        # FedBuff-style buffered-asynchronous server loop (DESIGN.md §7.4);
        # rs.engine picks the compiled scan or the event-driven reference.
        from .engine_async import run_scenario_buffered  # lazy: ↔ runner
        return run_scenario_buffered(
            sc, rs.strategy, algo_label=algo_label, rounds=rs.rounds,
            server_opt=rs.server_opt, server_lr=rs.server_lr,
            clients_per_round=rs.clients_per_round, beta=rs.beta,
            seed=rs.seed, eval_every=rs.eval_every,
            chunk_size=rs.chunk_size, ckpt_dir=rs.ckpt_dir,
            prox_mu=rs.prox_mu,
            positively_correlated=rs.positively_correlated,
            metrics_path=rs.metrics_path, fed_mode=rs.fed_mode,
            strategy_kwargs=rs.strategy_kwargs, completion=rs.completion,
            completion_kwargs=rs.completion_kwargs,
            buffer_size=rs.buffer_size,
            staleness_power=rs.staleness_power,
            staleness_discount=rs.staleness_discount,
            select_impl=rs.select_impl,
            engine=rs.engine, log_fn=log_fn)
    if rs.engine == "host" and rs.mesh_shape is not None:
        raise ValueError("mesh_shape= shards the device engine's client "
                         "dimension; it cannot apply to engine='host' (drop "
                         "mesh_shape or use engine='device')")
    fallback_reason = None
    if rs.engine == "device" and entry.host_only:
        fallback_reason = (
            f"strategy {algo_label!r} needs fresh per-client losses "
            f"computed on the host each round" if entry.needs_losses else
            f"strategy {algo_label!r} is registered host-only")
        warnings.warn(
            f"algorithm {algo_label!r} is not supported by the "
            f"{'sharded' if rs.mesh_shape is not None else 'device'} engine "
            f"({fallback_reason}); falling back to engine='host'",
            stacklevel=2)
    if rs.engine == "device" and fallback_reason is None:
        from .engine import run_scenario_device   # lazy: engine ↔ runner
        return run_scenario_device(
            sc, rs.strategy, algo_label=algo_label, rounds=rs.rounds,
            server_opt=rs.server_opt, server_lr=rs.server_lr,
            clients_per_round=rs.clients_per_round, beta=rs.beta,
            seed=rs.seed, eval_every=rs.eval_every,
            chunk_size=rs.chunk_size, ckpt_dir=rs.ckpt_dir,
            prox_mu=rs.prox_mu,
            positively_correlated=rs.positively_correlated,
            metrics_path=rs.metrics_path, fed_mode=rs.fed_mode,
            mesh=rs.mesh_shape, clients_axis=rs.clients_axis,
            model_axis=rs.model_axis,
            strategy_kwargs=rs.strategy_kwargs,
            completion=rs.completion,
            completion_kwargs=rs.completion_kwargs,
            select_impl=rs.select_impl, topk_impl=rs.topk_impl,
            log_fn=log_fn)

    task, fed, init, loss, acc = build_task(sc.task, rs.seed,
                                            **dict(sc.task_kwargs))
    rounds = rs.rounds or sc.rounds or task.rounds
    M = rs.clients_per_round or task.clients_per_round
    beta = rs.beta if rs.beta is not None else task.beta
    p = fed.p
    N = fed.n_clients

    avail_model = sc.build_availability(N, p=p)
    budget = sc.build_budget(default_k=M)
    comp_model = sc.build_completion(N, avail_model=avail_model,
                                     override=rs.completion,
                                     override_kwargs=rs.completion_kwargs)
    K_cohort = budget.k_max          # static cohort size: jit never resizes
    # engine-supplied defaults; explicit strategy_kwargs win on overlap
    hyper = dict(beta=beta, positively_correlated=rs.positively_correlated,
                 clients_per_round=M, select_impl=rs.select_impl)
    hyper.update(rs.strategy_kwargs)
    strategy = make_strategy(rs.strategy, N, p, **hyper)
    algo_state = strategy.init(N)    # built-ins calibrate r0 = M/N (Thm B.1)

    opt = make_optimizer(rs.server_opt, lr=rs.server_lr)
    key = jax.random.PRNGKey(rs.seed)
    params = init(key)
    opt_state = opt.init(params)
    fed_round = jax.jit(make_fed_round(loss, opt, mode="parallel",
                                       prox_mu=rs.prox_mu))
    eval_loss = jax.jit(loss)
    eval_acc = jax.jit(acc)

    sampler = CohortSampler(fed, cohort_size=K_cohort,
                            local_steps=task.local_steps,
                            local_batch=task.local_batch, seed=rs.seed)
    test_batch = {k: jnp.asarray(v) for k, v in fed.test_batch().items()}
    avail_state = avail_model.init()

    # PoC-style strategies: fresh per-client losses of the current global
    # model (the paper's PoC sends the model to d candidates who report
    # F_k(w_t); at paper scale we evaluate every client's train sample
    # directly).
    def fresh_losses(params):
        out = np.zeros(N, np.float32)
        for k in range(N):
            tr = fed.clients[k].train
            sub = {key_: jnp.asarray(v[:64]) for key_, v in tr.items()}
            out[k] = float(eval_loss(params, sub))
        return out

    metrics_file = None
    if rs.metrics_path:
        os.makedirs(os.path.dirname(os.path.abspath(rs.metrics_path)),
                    exist_ok=True)
        metrics_file = open(rs.metrics_path, "w")

    history = []
    sel_history = np.zeros((rounds, N), bool)
    comp_history = np.zeros((rounds, N), bool)
    t_start = time.time()
    t_first_round = None
    try:
        for t in range(rounds):
            # Split order shared with sim/engine.py — keep in lockstep or
            # the engine parity tests will catch the divergence.  The
            # completion key is *derived* (fold_in off k_sel), never split
            # from the main stream, so completion="always" reproduces
            # pre-completion trajectories bit-for-bit.
            key, k_av, k_sel, k_bud, k_batch = jax.random.split(key, 5)
            k_comp = jax.random.fold_in(k_sel, KEY_FOLD)
            avail_state, avail = avail_model.step(k_av, avail_state, t)
            k_t = budget.sample(k_bud, t)
            losses_in = (jnp.asarray(fresh_losses(params))
                         if strategy.needs_losses else None)
            complete_fn = (None if comp_model.trivial else
                           lambda m: comp_model.sample(k_comp, t, m))
            sel_mask, weights_full, algo_state = strategy.select(
                algo_state, k_sel, avail, k_t,
                SelectCtx(t=t, losses=losses_in, complete=complete_fn))
            sel_ids = np.flatnonzero(np.asarray(sel_mask))
            sel_history[t, sel_ids] = True
            # same pure draw as inside select — identical completed mask
            completed = (sel_mask if comp_model.trivial
                         else comp_model.sample(k_comp, t, sel_mask))
            comp_np = np.asarray(completed)
            comp_history[t] = comp_np

            batch_np, valid, ids = sampler.cohort_batch(sel_ids, key=k_batch)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            # dropped slots are zero-weighted regardless of whether the
            # strategy's finalize already renormalized over survivors
            w = jnp.asarray(np.asarray(weights_full)[ids] * valid
                            * comp_np[ids])
            lr_t = jnp.asarray(task.client_lr, jnp.float32)
            params, opt_state, metrics = fed_round(params, opt_state, batch,
                                                   w, lr_t)
            if t == 0:
                jax.block_until_ready(metrics.loss)
                t_first_round = time.time()

            record = dict(scenario=sc.name, algorithm=algo_label, round=t,
                          k_t=int(k_t), n_available=int(np.asarray(avail).sum()),
                          n_selected=int(len(sel_ids)),
                          n_completed=int(comp_np.sum()),
                          train_loss=float(metrics.loss),
                          delta_norm=float(metrics.delta_norm))
            if t % rs.eval_every == 0 or t == rounds - 1:
                record["test_loss"] = float(eval_loss(params, test_batch))
                record["test_acc"] = float(eval_acc(params, test_batch))
                history.append(dict(round=t, train_loss=record["train_loss"],
                                    test_loss=record["test_loss"],
                                    test_acc=record["test_acc"],
                                    n_selected=record["n_selected"],
                                    n_available=record["n_available"],
                                    n_completed=record["n_completed"]))
                log_fn(f"[{sc.name}/{algo_label}] round {t:4d} "
                       f"loss={record['test_loss']:.4f} "
                       f"acc={record['test_acc']:.4f} k_t={record['k_t']} "
                       f"sel={record['n_selected']} "
                       f"done={record['n_completed']} "
                       f"avail={record['n_available']}")
            if metrics_file:
                metrics_file.write(json.dumps(record) + "\n")
                metrics_file.flush()
            if rs.ckpt_dir and (t + 1) % 100 == 0:
                r_now = strategy_rates(strategy, algo_state)
                save_checkpoint(rs.ckpt_dir, t + 1,
                                {"params": params,
                                 "rates": (np.full(N, np.nan, np.float32)
                                           if r_now is None
                                           else np.asarray(r_now))})
    finally:
        if metrics_file:
            metrics_file.close()

    t_end = time.time()
    final = dict(history[-1]) if history else {}
    final["engine"] = "host"
    final["select_path"] = select_path(rs.select_impl, N)
    if fallback_reason is not None:
        final["engine_fallback"] = fallback_reason
    final["wall_s"] = t_end - t_start
    # scale accounting, mirroring the device engines: the host loop keeps
    # client data in numpy (nothing device-resident) and runs selection on
    # one process (no collective traffic).
    final["n_staged_bytes"] = 0
    final["selection_comm_bytes_per_round"] = 0
    # steady-state throughput: exclude round 0 (XLA compile of fed_round)
    if rounds > 1 and t_first_round is not None and t_end > t_first_round:
        final["steady_rounds_per_s"] = (rounds - 1) / (t_end - t_first_round)
    r_final = strategy_rates(strategy, algo_state)
    rates = (np.full(N, np.nan, np.float32) if r_final is None
             else np.asarray(r_final))
    return TrainResult(history=history, final_metrics=final,
                       rates=rates,
                       empirical_rates=sel_history.mean(0),
                       sel_history=sel_history,
                       comp_history=comp_history)
