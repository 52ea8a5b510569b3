"""Device-resident round engine: the whole federated round inside lax.scan.

``sim/runner.py`` executes rounds from a Python host loop — availability
step, selection, cohort gather, and metrics each cross the host↔device
boundary every round (``float(...)`` syncs, ``np.flatnonzero`` selection,
numpy batch assembly).  That is the right *reference* semantics, but on
small paper-scale models the host overhead dominates wall-clock and
serializes sweep cells.

This module compiles the entire round — availability ``step``, K_t budget
draw, the registered :class:`repro.core.strategies.SelectionStrategy`'s
pure ``select`` (state update + top-k under the budget included), the
mid-round completion draw (``sim/completion.py``: which selected clients
actually return; dropped slots are zero-weighted), device-side cohort
gather from pre-staged client data
(``data.pipeline.staged_cohort_batch``), and the jitted federated round —
into one ``lax.scan`` over a *chunk* of rounds.  Metrics stream out
per-chunk as stacked arrays instead of per-round scalars, so the host
touches the device once per chunk, not four times per round.

Parity with the host loop is exact by construction: both paths split the
round key the same way (avail / select / budget / batch) and draw minibatch
indices from the same ``jax.random.randint`` call, so the same seed yields
the same availability masks, K_t draws, selection masks, rate trajectories,
and batches (asserted in ``tests/test_engine.py``).

``run_cells_vmapped`` goes one step further: it vmaps the chunk program
over a (seed × budget-cap) batch axis, so one compiled executable runs an
entire sweep column of cells in lockstep — the workload shape of the
availability-regime grids in the paper's §4 and the related Markovian-
availability studies (PAPERS.md).

Not supported on the device path (falls back to the host loop via
``run_scenario(engine="host")``): strategies registered ``host_only`` /
``needs_losses`` (e.g. Power-of-Choice's fresh per-client losses) and
per-100-round checkpointing (the engine checkpoints at chunk boundaries
instead).
"""
from __future__ import annotations

import json
import os
import time
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import save_checkpoint
from ..core.bitmask import pack_bits, unpack_bits_np
from ..core.fedstep import make_fed_round
from ..core.selection import cohort_ids_from_mask
from ..core.spans import scope
from ..core.strategies import (SelectCtx, get_strategy_entry, make_strategy,
                               resolve_strategy, select_path, strategy_rates)
from ..data import CohortSampler
from ..data.pipeline import staged_cohort_batch, synth_cohort_batch
from ..data.synthetic import SynthTask
from ..optim import make_optimizer
from ..core.keys import COMPLETION as KEY_FOLD
from ..core.sanitize import guard_transfers
from ..sharding.rules import model_specs
from .scenario import Scenario, get_scenario

__all__ = ["DeviceEngine", "build_engine", "run_scenario_device",
           "run_cells_vmapped"]


class EngineCarry(NamedTuple):
    """The lax.scan carry: everything that persists across rounds."""
    key: jax.Array
    params: object
    opt_state: object
    algo_state: object
    avail_state: object


class RoundStream(NamedTuple):
    """Per-round outputs stacked along the chunk axis by lax.scan.

    Per-round rate trajectories are deliberately not streamed: r(t) is a
    deterministic EMA of the streamed *completed* masks, so consumers can
    reconstruct it exactly, and the final r(T) lives in the carry.
    ``completed`` equals ``sel_mask`` under ``completion="always"`` and is
    streamed anyway — a duplicate mask per round is cheap next to one
    stream structure shared by every engine, driver, and test.

    The two masks stream *bit-packed* — (C, ceil(N/32)) uint32 words
    (``core.bitmask``, 8× less device→host traffic per chunk than (C, N)
    bool at million-client N); the drivers unpack once per chunk
    (``unpack_bits_np``: a byte-wise ``np.unpackbits`` that writes the
    (C, N) bool mask once) before any consumer sees them, so everything
    downstream of a driver still works on (C, N) bool.
    """
    sel_mask: jnp.ndarray      # (C, ceil(N/32)) u32 — packed cohort S_t
    completed: jnp.ndarray     # (C, ceil(N/32)) u32 — packed survivors ⊆ S_t
    k_t: jnp.ndarray           # (C,) int32
    n_available: jnp.ndarray   # (C,) int32
    train_loss: jnp.ndarray    # (C,) f32
    delta_norm: jnp.ndarray    # (C,) f32


def _unpack_stream(out_np: "RoundStream", n: int) -> "RoundStream":
    """Driver-side decode of one chunk's streams: packed masks → (C, n)
    bool (bits past ``n`` — client-dim padding — are never set).  Traced
    as ``stream_decode``; ``bytes`` counts the bool bytes it produces."""
    rows = int(np.prod(out_np.sel_mask.shape[:-1]))
    with jax.profiler.TraceAnnotation("stream_decode", clients=n,
                                      bytes=2 * rows * n):
        return out_np._replace(sel_mask=unpack_bits_np(out_np.sel_mask, n),
                               completed=unpack_bits_np(out_np.completed, n))


def _pull(out) -> "RoundStream":
    """Device → host copy of one chunk's streams, traced as
    ``stream_pull``; ``bytes`` counts the (packed) bytes pulled."""
    nbytes = sum(int(x.nbytes) for x in jax.tree.leaves(out))
    with jax.profiler.TraceAnnotation("stream_pull", bytes=nbytes):
        return jax.tree.map(np.asarray, out)


def _staged_nbytes(staged) -> int:
    """Resident device bytes of a staged client dataset (0 when the data
    is synthesized on demand — nothing is resident)."""
    if isinstance(staged, SynthTask):
        return 0
    return int(sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in staged.arrays.values())
               + int(staged.counts.shape[0]) * staged.counts.dtype.itemsize)


class DeviceEngine:
    """One compiled (scenario × algorithm × task) cell.

    ``chunk(carry, ts, k_cap)`` advances ``len(ts)`` rounds in one XLA
    program; ``init_carry(key)`` builds the round-0 state for a cell seed.
    ``k_cap`` is a traced scalar upper bound on K_t (pass ``k_max`` for a
    no-op) — it is the scenario-parameter axis `run_cells_vmapped` sweeps.
    """

    def __init__(self, *, avail_model, budget, strategy, staged, fed_round,
                 init_params, opt, client_lr, local_steps, local_batch,
                 completion=None):
        self.avail_model = avail_model
        self.budget = budget
        self.strategy = strategy
        self.completion = completion
        self.k_max = budget.k_max
        synth = isinstance(staged, SynthTask)
        self.n_clients = (staged.n_clients if synth
                          else int(staged.counts.shape[0]))
        self.n_staged_bytes = _staged_nbytes(staged)
        self.selection_comm_bytes_per_round = 0   # single device: no comm
        trivial = completion is None or completion.trivial

        def complete_draw(key, t, sel_mask):
            with scope("complete"):
                return completion.sample(key, t, sel_mask)

        def cohort_batch(key, ids):
            if synth:
                return synth_cohort_batch(staged, key, ids, local_steps,
                                          local_batch)
            return staged_cohort_batch(staged, key, ids, local_steps,
                                       local_batch)

        def round_step(carry, t, k_cap):
            # Same split order as the host loop in runner.py — parity.  The
            # completion key is derived (fold_in), never split from the
            # main stream: completion="always" stays bit-identical.
            key, k_av, k_sel, k_bud, k_batch = jax.random.split(carry.key, 5)
            k_comp = jax.random.fold_in(k_sel, KEY_FOLD)
            with scope("avail"):
                avail_state, avail = avail_model.step(k_av,
                                                      carry.avail_state, t)
            with scope("budget"):
                k_t = jnp.minimum(budget.sample(k_bud, t),
                                  jnp.asarray(k_cap, jnp.int32))
            complete_fn = (None if trivial else
                           lambda m: complete_draw(k_comp, t, m))
            with scope("select"):
                sel_mask, w_full, algo_state = strategy.select(
                    carry.algo_state, k_sel, avail, k_t,
                    SelectCtx(t=t, complete=complete_fn))
            # same pure draw as inside select — identical completed mask
            completed = sel_mask if trivial else complete_fn(sel_mask)
            with scope("cohort"):
                ids, valid = cohort_ids_from_mask(sel_mask, budget.k_max)
                batch = cohort_batch(k_batch, ids)
                w = w_full[ids] * valid
                if not trivial:
                    # dropped slots contribute nothing even if the
                    # strategy's finalize ignored the completion hook
                    w = w * completed[ids]
            params, opt_state, m = fed_round(
                carry.params, carry.opt_state, batch, w,
                jnp.asarray(client_lr, jnp.float32))
            with scope("stream"):
                out = RoundStream(sel_mask=pack_bits(sel_mask),
                                  completed=pack_bits(completed),
                                  k_t=k_t,
                                  n_available=avail.sum().astype(jnp.int32),
                                  train_loss=m.loss, delta_norm=m.delta_norm)
            return EngineCarry(key, params, opt_state, algo_state,
                               avail_state), out

        def chunk(carry, ts, k_cap):
            return jax.lax.scan(lambda c, t: round_step(c, t, k_cap),
                                carry, ts)

        self._chunk = jax.jit(chunk)
        self._vchunk = jax.jit(jax.vmap(chunk, in_axes=(0, None, 0)))
        # Device-resident default cap, staged at build time: drivers call
        # chunk() inside the sanitizer transfer guard, so the default must
        # not be a fresh host->device transfer per chunk.
        self._k_max_dev = jnp.asarray(self.k_max, jnp.int32)

        def _make_init(r0):
            def init_carry(key):
                params = init_params(key)
                return EngineCarry(key=key, params=params,
                                   opt_state=opt.init(params),
                                   algo_state=strategy.init(self.n_clients,
                                                            r0=r0),
                                   avail_state=avail_model.init())
            return init_carry

        self._make_init = _make_init
        self.init_carry = _make_init(None)

    def set_r0(self, r0: float) -> None:
        """Pin the rate-EMA initialization (runner uses the calibrated M/N)."""
        self.init_carry = self._make_init(r0)

    def chunk(self, carry, ts, k_cap=None):
        """Advance one chunk of rounds; returns (carry', RoundStream)."""
        k_cap = (self._k_max_dev if k_cap is None
                 else jnp.asarray(k_cap, jnp.int32))
        with jax.profiler.TraceAnnotation("chunk_dispatch", rounds=len(ts)):
            return self._chunk(carry, ts, k_cap)

    def vmapped_chunk(self, carries, ts, k_caps):
        """Batched chunk over the leading cell axis of ``carries``/``k_caps``."""
        k_caps = jnp.asarray(k_caps, jnp.int32)
        with jax.profiler.TraceAnnotation(
                "chunk_dispatch", rounds=len(ts) * int(k_caps.shape[0])):
            return self._vchunk(carries, ts, k_caps)


def build_engine(scenario: Union[str, Scenario], algo_name: str = "f3ast", *,
                 seed: int = 0, clients_per_round: Optional[int] = None,
                 beta: Optional[float] = None, server_opt: str = "sgd",
                 server_lr: Optional[float] = None, prox_mu: float = 0.0,
                 positively_correlated: bool = False,
                 fed_mode: str = "parallel",
                 mesh=None, clients_axis: str = "clients",
                 model_axis: str = "model",
                 strategy_kwargs=None,
                 completion: Optional[str] = None, completion_kwargs=None,
                 select_impl: str = "xla", topk_impl: str = "stream"):
    """Build the compiled cell for one (scenario × strategy).

    Returns ``(engine, ctx)`` where ``ctx`` carries the task pieces the
    drivers need host-side (eval fns, test batch, rounds default, N).
    ``seed`` here selects the *data* realization; per-cell model seeds are
    what ``init_carry`` takes.  ``algo_name`` is resolved through the
    strategy registry (aliases like ``fedadam`` rewrite to their base
    strategy + server optimizer; unknown names raise ``KeyError``).

    ``mesh`` (a Mesh, a shard count, a 1- or 2-tuple shape, or ``<= 0`` /
    ``(0,)`` for every device) selects the client-sharded engine
    (:mod:`repro.sim.engine_sharded`): the N dimension of availability
    state, selection, and staged data is partitioned over the
    ``clients_axis`` mesh axis.  A 2-tuple ``(c, m)`` (or a prebuilt Mesh
    naming ``model_axis``) additionally shards each stored parameter and
    optimizer-state leaf over the ``model_axis`` per
    ``sharding.rules.model_specs`` — the two-axis federated mesh of
    DESIGN.md §7.2.  Same seed ⇒ same selection masks / rates / losses as
    the unsharded engine on any mesh shape.
    ``topk_impl`` picks the sharded engine's distributed top-k reduction
    (``"stream"`` — default, O(k) butterfly/ring exchange — or
    ``"allgather"``, the legacy full-(N,) gather); both produce bitwise-
    identical masks, and the flag is ignored off-mesh.
    """
    from .runner import build_task   # local import: runner ↔ engine
    from .engine_sharded import ShardedEngine, resolve_client_mesh

    mesh = resolve_client_mesh(mesh, clients_axis, model_axis)
    if mesh is not None and select_impl == "pallas":
        raise ValueError(
            "select_impl='pallas' fuses the single-device top-k cut; the "
            "client-sharded engine keeps its distributed sharded_topk_mask "
            "(drop mesh= or use select_impl='xla')")
    sc = get_scenario(scenario)
    algo_name, server_opt, server_lr = resolve_strategy(algo_name, server_opt,
                                                        server_lr)
    if get_strategy_entry(algo_name).host_only:
        raise ValueError(
            f"strategy {algo_name!r} is host-only (needs per-round host "
            f"state); use run_scenario(engine='host')")
    task, fed, init, loss, acc = build_task(sc.task, seed,
                                            **dict(sc.task_kwargs))
    n = fed.n_clients
    p = fed.p
    m = clients_per_round or task.clients_per_round
    beta = beta if beta is not None else task.beta

    avail_model = sc.build_availability(n, p=p)
    budget = sc.build_budget(default_k=m)
    comp_model = sc.build_completion(n, avail_model=avail_model,
                                     override=completion,
                                     override_kwargs=completion_kwargs)
    # engine-supplied defaults; explicit strategy_kwargs win on overlap
    hyper = dict(beta=beta, positively_correlated=positively_correlated,
                 clients_per_round=m, select_impl=select_impl)
    hyper.update(strategy_kwargs or {})
    strategy = make_strategy(algo_name, n, p, **hyper)
    opt = make_optimizer(server_opt, lr=server_lr)

    sampler = CohortSampler(fed, cohort_size=budget.k_max,
                            local_steps=task.local_steps,
                            local_batch=task.local_batch, seed=seed)
    common = dict(avail_model=avail_model, budget=budget, strategy=strategy,
                  init_params=init, opt=opt, client_lr=task.client_lr,
                  local_steps=task.local_steps,
                  local_batch=task.local_batch, completion=comp_model)
    if mesh is not None:
        if fed_mode != "parallel":
            raise ValueError("the client-sharded engine runs the cohort in "
                             "parallel mode only (the mesh axis carries the "
                             f"cohort split); got fed_mode={fed_mode!r}")
        use_model = model_axis in mesh.axis_names
        if use_model:
            # Per-leaf model-parallel layout, computed once from the param
            # shapes; ShardedEngine re-derives the identical tree for its
            # carry specs (model_specs is deterministic in (shapes, mesh)).
            p_shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
            p_specs = model_specs(p_shapes, mesh, model_axis=model_axis)
        fed_round = make_fed_round(loss, opt, mode="parallel",
                                   prox_mu=prox_mu,
                                   cohort_axis=clients_axis,
                                   cohort_slots=budget.k_max,
                                   model_axis=model_axis if use_model
                                   else None,
                                   param_specs=p_specs if use_model
                                   else None)
        engine = ShardedEngine(mesh=mesh, axis=clients_axis,
                               model_axis=model_axis if use_model else None,
                               staged=sampler.stage_device(
                                   mesh=mesh, axis=clients_axis),
                               fed_round=fed_round, n_clients=n,
                               topk_impl=topk_impl, **common)
    else:
        fed_round = make_fed_round(loss, opt, mode=fed_mode, prox_mu=prox_mu)
        engine = DeviceEngine(staged=sampler.stage_device(),
                              fed_round=fed_round, **common)
    # r0 needs no pinning here: make_strategy received clients_per_round, so
    # the built-in strategies' init() self-calibrates to the same M/N.

    ctx = dict(scenario=sc, task=task, n_clients=n,
               rounds_default=sc.rounds or task.rounds,
               eval_loss=jax.jit(loss), eval_acc=jax.jit(acc),
               test_batch={k: jnp.asarray(v)
                           for k, v in fed.test_batch().items()})
    return engine, ctx


def _final_rates(engine, carry, n_real: int) -> np.ndarray:
    """Tracked (..., N) rates from the carry, NaN for rate-free strategies."""
    r = strategy_rates(engine.strategy, carry.algo_state)
    if r is None:
        shape = np.shape(carry.key)[:-1] + (n_real,)   # vmapped cell axes
        return np.full(shape, np.nan, np.float32)
    return np.asarray(r)[..., :n_real]


def _chunk_spans(rounds: int, chunk_size: int):
    """Split [0, rounds) into contiguous spans of at most chunk_size."""
    spans = []
    t0 = 0
    while t0 < rounds:
        t1 = min(t0 + chunk_size, rounds)
        spans.append((t0, t1))
        t0 = t1
    return spans


def run_scenario_device(scenario: Union[str, Scenario],
                        algo_name: str = "f3ast", *,
                        rounds: Optional[int] = None,
                        server_opt: str = "sgd", server_lr: float = 1.0,
                        clients_per_round: Optional[int] = None,
                        beta: Optional[float] = None, seed: int = 0,
                        eval_every: int = 10,
                        chunk_size: Optional[int] = None,
                        ckpt_dir: Optional[str] = None,
                        prox_mu: float = 0.0,
                        positively_correlated: bool = False,
                        metrics_path: Optional[str] = None,
                        fed_mode: str = "parallel",
                        mesh=None, clients_axis: str = "clients",
                        model_axis: str = "model",
                        strategy_kwargs=None,
                        completion: Optional[str] = None,
                        completion_kwargs=None,
                        select_impl: str = "xla",
                        topk_impl: str = "stream",
                        algo_label: Optional[str] = None,
                        log_fn=print):
    """Device-resident drop-in for ``runner.run_scenario``.

    ``mesh`` routes through the client-sharded engine (see
    :func:`build_engine`); results are identical for the same seed.

    Semantics differences vs. the host loop (documented, tested):
      * evaluation happens at the end of any chunk containing an
        ``eval_every`` round, plus always after the final round
        (``chunk_size`` defaults to ``eval_every``, so the cadence matches
        the host up to a one-round offset: the host evals after rounds
        0, 10, ...; the engine after rounds 9, 19, ...);
      * ``chunk_size`` is a performance knob, not a semantic one: params
        only materialize on the host at chunk boundaries, so it is capped
        at ``eval_every`` to keep the requested eval cadence intact;
      * checkpoints (if ``ckpt_dir``) are written at chunk boundaries.
    Selection masks, rates, and losses match the host loop exactly for the
    same seed (``tests/test_engine.py``).
    """
    engine, ctx = build_engine(scenario, algo_name, seed=seed,
                               clients_per_round=clients_per_round,
                               beta=beta, server_opt=server_opt,
                               server_lr=server_lr, prox_mu=prox_mu,
                               positively_correlated=positively_correlated,
                               fed_mode=fed_mode, mesh=mesh,
                               clients_axis=clients_axis,
                               model_axis=model_axis,
                               strategy_kwargs=strategy_kwargs,
                               completion=completion,
                               completion_kwargs=completion_kwargs,
                               select_impl=select_impl,
                               topk_impl=topk_impl)
    engine_label = "sharded" if mesh is not None else "device"
    n_real = engine.n_clients
    sc, task = ctx["scenario"], ctx["task"]
    rounds = rounds or ctx["rounds_default"]
    chunk_size = max(1, min(chunk_size or eval_every, eval_every, rounds))
    algo_label = algo_label or algo_name

    carry = engine.init_carry(jax.random.PRNGKey(seed))

    metrics_file = None
    if metrics_path:
        os.makedirs(os.path.dirname(os.path.abspath(metrics_path)),
                    exist_ok=True)
        metrics_file = open(metrics_path, "w")

    history = []
    streams = []
    t_start = time.time()
    t_first_chunk = None
    try:
        for (t0, t1) in _chunk_spans(rounds, chunk_size):
            ts = jnp.arange(t0, t1, dtype=jnp.int32)
            # Under REPRO_SANITIZE=1 any stray implicit host<->device
            # transfer inside the compiled chunk raises (core.sanitize).
            with guard_transfers():
                carry, out = engine.chunk(carry, ts)
            # One host↔device sync per chunk: pull the streamed metrics
            # (masks cross packed — unpack once here, see RoundStream).
            out_np = _unpack_stream(_pull(out), n_real)
            if t_first_chunk is None:
                t_first_chunk = time.time()
            streams.append(out_np)

            # eval_every sets the cadence; the chunk boundary only sets
            # where within the cadence the eval lands.
            do_eval = (t1 == rounds
                       or any(t % eval_every == 0 for t in range(t0, t1)))
            if do_eval:
                with jax.profiler.TraceAnnotation("eval"):
                    test_loss = float(ctx["eval_loss"](carry.params,
                                                       ctx["test_batch"]))
                    test_acc = float(ctx["eval_acc"](carry.params,
                                                     ctx["test_batch"]))
                history.append(dict(round=t1 - 1,
                                    train_loss=float(out_np.train_loss[-1]),
                                    test_loss=test_loss, test_acc=test_acc,
                                    n_selected=int(out_np.sel_mask[-1].sum()),
                                    n_available=int(out_np.n_available[-1]),
                                    n_completed=int(out_np.completed[-1].sum())))
                log_fn(f"[{sc.name}/{algo_label}] round {t1 - 1:4d} "
                       f"loss={test_loss:.4f} acc={test_acc:.4f} "
                       f"k_t={int(out_np.k_t[-1])} "
                       f"sel={history[-1]['n_selected']} "
                       f"done={history[-1]['n_completed']} "
                       f"avail={history[-1]['n_available']}")
            if metrics_file:
                with jax.profiler.TraceAnnotation("metrics_write"):
                    for i, t in enumerate(range(t0, t1)):
                        record = dict(
                            scenario=sc.name, algorithm=algo_label,
                            round=t, k_t=int(out_np.k_t[i]),
                            n_available=int(out_np.n_available[i]),
                            n_selected=int(out_np.sel_mask[i].sum()),
                            n_completed=int(out_np.completed[i].sum()),
                            train_loss=float(out_np.train_loss[i]),
                            delta_norm=float(out_np.delta_norm[i]))
                        if do_eval and t == t1 - 1:
                            record["test_loss"] = test_loss
                            record["test_acc"] = test_acc
                        metrics_file.write(json.dumps(record) + "\n")
                    metrics_file.flush()
            if ckpt_dir:
                with jax.profiler.TraceAnnotation("checkpoint"):
                    save_checkpoint(ckpt_dir, t1,
                                    {"params": carry.params,
                                     "rates": _final_rates(engine, carry,
                                                           n_real)})
    finally:
        if metrics_file:
            metrics_file.close()

    from .runner import TrainResult   # local import: runner ↔ engine
    sel_history = np.concatenate([s.sel_mask for s in streams],
                                 axis=0)[:, :n_real]
    comp_history = np.concatenate([s.completed for s in streams],
                                  axis=0)[:, :n_real]
    t_end = time.time()
    final = dict(history[-1])
    final["engine"] = engine_label
    final["select_path"] = select_path(select_impl, n_real)
    final["wall_s"] = t_end - t_start
    # scale accounting (ISSUE 8): resident staged-data bytes (0 when
    # cohorts are synthesized on demand) and per-round selection traffic.
    final["n_staged_bytes"] = engine.n_staged_bytes
    final["selection_comm_bytes_per_round"] = (
        engine.selection_comm_bytes_per_round)
    # steady-state throughput: exclude the first chunk (XLA compile)
    steady_rounds = rounds - min(chunk_size, rounds)
    if steady_rounds > 0 and t_end > t_first_chunk:
        final["steady_rounds_per_s"] = steady_rounds / (t_end - t_first_chunk)
    return TrainResult(history=history, final_metrics=final,
                       rates=_final_rates(engine, carry, n_real),
                       empirical_rates=sel_history.mean(0),
                       sel_history=sel_history,
                       comp_history=comp_history)


def run_cells_vmapped(scenario: Union[str, Scenario],
                      algo_name: str = "f3ast", *,
                      seeds: Sequence[int] = (0,),
                      k_caps: Optional[Sequence[int]] = None,
                      rounds: Optional[int] = None,
                      chunk_size: int = 32, data_seed: Optional[int] = None,
                      **build_kwargs):
    """Run a batch of cells as ONE compiled vmapped program.

    The batch axis is (seed × budget-cap): cell ``i`` runs with model/PRNG
    seed ``seeds[i]`` under K_t capped at ``k_caps[i]`` (default: no cap).
    All cells share one data realization (``data_seed``, default
    ``seeds[0]``) and one availability/budget/task spec — the sweep column
    of a (scenario-param × seed) grid.  Returns a dict of stacked per-cell
    results; wall-clock is one chunk-program execution per chunk span, not
    per cell.
    """
    seeds = list(seeds)
    n_cells = len(seeds)
    if k_caps is None:
        k_caps_arr = None
    else:
        assert len(k_caps) == n_cells, (len(k_caps), n_cells)
        k_caps_arr = jnp.asarray(list(k_caps), jnp.int32)

    engine, ctx = build_engine(scenario, algo_name,
                               seed=seeds[0] if data_seed is None
                               else data_seed,
                               **build_kwargs)
    if k_caps_arr is None:
        k_caps_arr = jnp.full((n_cells,), engine.k_max, jnp.int32)
    rounds = rounds or ctx["rounds_default"]

    carries = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[engine.init_carry(jax.random.PRNGKey(s)) for s in seeds])

    streams = []
    t_start = time.time()
    t_first_chunk = None
    for (t0, t1) in _chunk_spans(rounds, chunk_size):
        ts = jnp.arange(t0, t1, dtype=jnp.int32)
        carries, out = engine.vmapped_chunk(carries, ts, k_caps_arr)
        streams.append(_unpack_stream(_pull(out), engine.n_clients))
        if t_first_chunk is None:
            t_first_chunk = time.time()
    t_end = time.time()

    test_loss = np.asarray(jax.vmap(ctx["eval_loss"], in_axes=(0, None))(
        carries.params, ctx["test_batch"]))
    test_acc = np.asarray(jax.vmap(ctx["eval_acc"], in_axes=(0, None))(
        carries.params, ctx["test_batch"]))
    sel_history = np.concatenate([s.sel_mask for s in streams], axis=1)
    comp_history = np.concatenate([s.completed for s in streams], axis=1)
    train_loss = np.concatenate([s.train_loss for s in streams], axis=1)
    result = dict(seeds=list(seeds), k_caps=np.asarray(k_caps_arr).tolist(),
                  rounds=rounds, test_loss=test_loss, test_acc=test_acc,
                  train_loss=train_loss,             # (cells, T)
                  sel_history=sel_history,           # (cells, T, N)
                  comp_history=comp_history,         # (cells, T, N)
                  rates=_final_rates(engine, carries, engine.n_clients),
                  empirical_rates=sel_history.mean(axis=1),
                  wall_s=t_end - t_start)
    steady_rounds = rounds - min(chunk_size, rounds)
    if steady_rounds > 0 and t_end > t_first_chunk:
        result["steady_rounds_per_s"] = (
            steady_rounds * n_cells / (t_end - t_first_chunk))
    return result
