"""Asynchronous buffered-aggregation engine (FedBuff-style server loop).

Every other engine in this repo is round-synchronous: a round ends when the
cohort's survivors report, so a single straggler stretches the whole round
(the `deadline` completion process models exactly that cutoff).  Production
FL under intermittent availability instead runs *buffered asynchronous*
aggregation (Nguyen et al., FedBuff): the server dispatches work whenever it
selects clients, client updates arrive whenever their latency elapses, and
the server applies one update as soon as a *buffer* of M arrivals has
filled, discounting stale contributions.

This module promotes the per-client lognormal latency draws that
``sim/completion.py`` already makes (``DeadlineCompletion``) to first-class
arrival times and runs that loop two ways:

* a **host reference loop** (``engine="host"``): an event-driven Python
  loop over a sorted pending-arrival list — the readable ground truth;
* a **compiled device path** (``engine="device"``): the same semantics as
  one ``lax.scan`` over server steps with a fixed-capacity arrival pool
  kept sorted by a 3-pass stable argsort.

Semantics (DESIGN.md §7.4; both paths implement these bit-identically):

* Server step t: split the round key exactly like the sync engines
  (avail / select / budget / batch) and derive the latency key as
  ``fold_in(k_sel, KEY_FOLD)`` — the same derived stream the completion
  draw uses, so a buffered run's latency for client k at step t *is* the
  latency the `deadline` process would have thresholded.
* Selected clients are *dispatched*: an arrival (time = t + latency,
  client, dispatch step) enters the pending pool.  The
  strategy's rate EMA therefore tracks dispatches (``SelectCtx.complete``
  is not threaded — there is no within-step completion in a buffered
  server).
* The pool is ordered by (arrival time, client id, dispatch step) — a
  total order, so host and device agree on ties bit-for-bit.  The pool
  has fixed capacity; when it overflows, the *latest* arrivals are
  dropped (counted per step as ``n_overflow`` — a device that falls that
  far behind is treated as having abandoned the round).
* The server step aggregates the first ``buffer_size`` pending arrivals
  with weights ``discount(staleness)`` normalized over the buffer, where
  ``staleness = t - dispatch_step`` (the number of server steps the update
  waited) and ``discount`` comes from the pluggable
  ``STALENESS_DISCOUNTS`` registry (default polynomial ``1/(1+s)^power``;
  the weights depend only on integer staleness, which is what makes them
  bit-identical across the host and device paths).  Updates are computed from the *current* params at
  flush time — the standard first-order simulation of async training at
  paper scale (the staleness discount is what models the degradation).
* Fewer than ``buffer_size`` pending arrivals is fine: the missing slots
  are zero-weighted exactly like an underfull synchronous cohort.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import save_checkpoint
from ..core.fedstep import make_fed_round
from ..core.selection import cohort_ids_from_mask
from ..core.spans import scope
from ..core.strategies import (SelectCtx, get_strategy_entry, make_strategy,
                               resolve_strategy, select_path, strategy_rates)
from ..data import CohortSampler
from ..data.pipeline import staged_cohort_batch
from ..optim import make_optimizer
from ..core.keys import COMPLETION as KEY_FOLD
from ..core.sanitize import guard_transfers
from .engine import _pull
from .scenario import Scenario, get_scenario

__all__ = ["STALENESS_DISCOUNTS", "ArrivalPool", "AsyncCarry", "AsyncEngine",
           "AsyncStream", "register_staleness_discount",
           "run_scenario_buffered", "staleness_weights"]


# ---------------------------------------------------------------------------
# Staleness discounts — pluggable, mirroring the strategy/completion registries
# ---------------------------------------------------------------------------

STALENESS_DISCOUNTS: Dict[str, Callable] = {}


def register_staleness_discount(name: str, fn: Callable) -> Callable:
    """Register ``fn(staleness_f32, power) -> discount`` under ``name``.

    ``fn`` must be a pure jnp function of a float32 staleness array; both
    the host and device paths call the *same* registered function, which is
    what makes the aggregation weights bit-identical across engines.
    """
    STALENESS_DISCOUNTS[str(name).lower()] = fn
    return fn


register_staleness_discount("polynomial", lambda s, p: (1.0 + s) ** (-p))
register_staleness_discount("exponential", lambda s, p: jnp.exp(-p * s))


def staleness_weights(staleness, valid, power: float,
                      discount: str = "polynomial") -> jnp.ndarray:
    """Normalized buffer weights: ``discount(staleness)`` on valid slots,
    renormalized to sum to 1 (all-zero when the buffer is empty).

    The weights are a pure function of the integer staleness values and the
    valid mask — deliberately independent of any float strategy state, so
    the host and device paths (which call this same jnp function) agree
    bit-for-bit.  FedBuff semantics: within the buffer, contributions are
    uniform up to the staleness discount; the selection strategy's weights
    govern *who gets dispatched*, not the buffered average.
    """
    if discount not in STALENESS_DISCOUNTS:
        raise KeyError(f"unknown staleness discount {discount!r}; "
                       f"known: {sorted(STALENESS_DISCOUNTS)}")
    fn = STALENESS_DISCOUNTS[discount]
    s = jnp.asarray(staleness, jnp.float32)
    valid = jnp.asarray(valid, bool)
    raw = jnp.where(valid, fn(s, power), 0.0)
    total = raw.sum()
    return jnp.where(total > 0, raw / jnp.where(total > 0, total, 1.0), 0.0)


def default_pool_slots(buffer_size: int, k_max: int) -> int:
    """Pending-pool capacity: room for the buffer plus ~4 dispatch waves of
    in-flight updates (steady-state backlog at unit-scale latencies)."""
    return int(buffer_size + 4 * k_max)


# ---------------------------------------------------------------------------
# The pending-arrival pool (device representation)
# ---------------------------------------------------------------------------

class ArrivalPool(NamedTuple):
    """Fixed-capacity pending-update pool, kept sorted by (time, cid, round).

    Empty slots are (time=+inf, cid=N sentinel, round=0, valid=False) so
    they sort after every real arrival.
    """
    time: jnp.ndarray      # (P,) f32 arrival time in server-step units
    cid: jnp.ndarray       # (P,) i32 client id (N = empty sentinel)
    round: jnp.ndarray     # (P,) i32 dispatch server step
    valid: jnp.ndarray     # (P,) bool


def empty_pool(pool_slots: int, n_clients: int) -> ArrivalPool:
    return ArrivalPool(
        time=jnp.full((pool_slots,), jnp.inf, jnp.float32),
        cid=jnp.full((pool_slots,), n_clients, jnp.int32),
        round=jnp.zeros((pool_slots,), jnp.int32),
        valid=jnp.zeros((pool_slots,), bool))


def _lex_order(time, cid, rnd):
    """Stable argsort by primary ``time``, then ``cid``, then ``rnd`` —
    the device-side equivalent of ``sorted(key=(time, cid, rnd))`` on the
    host (three stable passes, least-significant key first)."""
    o = jnp.argsort(rnd, stable=True)
    o = o[jnp.argsort(cid[o], stable=True)]
    o = o[jnp.argsort(time[o], stable=True)]
    return o


def pool_insert(pool: ArrivalPool, new: ArrivalPool):
    """Merge ``new`` arrivals into the pool; re-sort; truncate to capacity.

    Returns ``(pool', n_overflow)`` where ``n_overflow`` counts valid
    arrivals dropped because the pool was full — by construction the
    *latest* entries in the (time, cid, round) order.
    """
    p_slots = pool.time.shape[0]
    cat = ArrivalPool(*[jnp.concatenate([a, b])
                        for a, b in zip(pool, new)])
    order = _lex_order(cat.time, cat.cid, cat.round)
    cat = ArrivalPool(*[a[order] for a in cat])
    n_overflow = jnp.maximum(
        cat.valid.sum().astype(jnp.int32) - p_slots, 0)
    return ArrivalPool(*[a[:p_slots] for a in cat]), n_overflow


def pool_flush(pool: ArrivalPool, buffer_size: int, t, n_clients: int):
    """Pop the first ``buffer_size`` pending arrivals (the buffer).

    Returns ``(pool', buf_ids, buf_valid, buf_staleness)``.
    ``buf_ids`` mirrors the synchronous cohort convention
    (``cohort_ids_from_mask``): invalid slots repeat the first buffered
    client; an empty buffer clamps to client N-1, all-invalid.
    """
    m = buffer_size
    buf = ArrivalPool(*[a[:m] for a in pool])
    buf_valid = buf.valid
    first = jnp.where(buf_valid[0], buf.cid[0], n_clients - 1)
    buf_ids = jnp.where(buf_valid, buf.cid, first).astype(jnp.int32)
    staleness = jnp.where(
        buf_valid, jnp.asarray(t, jnp.int32) - buf.round, 0).astype(jnp.int32)
    empties = empty_pool(m, n_clients)
    rest = ArrivalPool(*[jnp.concatenate([a[m:], e])
                         for a, e in zip(pool, empties)])
    return rest, buf_ids, buf_valid, staleness


# ---------------------------------------------------------------------------
# The compiled engine
# ---------------------------------------------------------------------------

class AsyncCarry(NamedTuple):
    """The lax.scan carry: sync-engine state plus the pending-arrival pool."""
    key: jax.Array
    params: object
    opt_state: object
    algo_state: object
    avail_state: object
    pool: ArrivalPool


class AsyncStream(NamedTuple):
    """Per-server-step outputs stacked along the chunk axis by lax.scan."""
    sel_mask: jnp.ndarray       # (C, N) bool — dispatched this step
    buf_ids: jnp.ndarray        # (C, M) i32 — aggregated clients (padded)
    buf_valid: jnp.ndarray      # (C, M) bool
    buf_staleness: jnp.ndarray  # (C, M) i32 — t - dispatch step
    buf_weights: jnp.ndarray    # (C, M) f32 — normalized aggregation weights
    k_t: jnp.ndarray            # (C,) i32
    n_available: jnp.ndarray    # (C,) i32
    n_buffered: jnp.ndarray     # (C,) i32
    mean_staleness: jnp.ndarray  # (C,) f32 (0 when the buffer is empty)
    n_overflow: jnp.ndarray     # (C,) i32 — arrivals dropped at capacity
    train_loss: jnp.ndarray     # (C,) f32
    delta_norm: jnp.ndarray     # (C,) f32


class AsyncEngine:
    """One compiled buffered-aggregation cell (scenario × strategy × task).

    ``chunk(carry, ts)`` advances ``len(ts)`` server steps in one XLA
    program; ``init_carry(key)`` builds the step-0 state (empty pool).
    """

    def __init__(self, *, avail_model, budget, strategy, staged, fed_round,
                 init_params, opt, client_lr, local_steps, local_batch,
                 arrival, buffer_size, staleness_power=0.5,
                 staleness_discount="polynomial", pool_slots=None):
        self.avail_model = avail_model
        self.budget = budget
        self.strategy = strategy
        self.arrival = arrival
        self.k_max = budget.k_max
        self.n_clients = int(staged.counts.shape[0])
        self.buffer_size = int(buffer_size)
        self.pool_slots = int(pool_slots or
                              default_pool_slots(buffer_size, budget.k_max))
        self.staleness_power = float(staleness_power)
        self.staleness_discount = str(staleness_discount)
        n = self.n_clients

        def round_step(carry, t):
            # Same split order as every other engine — parity.  The latency
            # key is derived (fold_in off k_sel, the completion stream), so
            # buffered latencies equal the deadline process's own draws and
            # the main avail/select/budget/batch streams are untouched.
            key, k_av, k_sel, k_bud, k_batch = jax.random.split(carry.key, 5)
            k_arr = jax.random.fold_in(k_sel, KEY_FOLD)
            with scope("avail"):
                avail_state, avail = avail_model.step(k_av,
                                                      carry.avail_state, t)
            with scope("budget"):
                k_t = budget.sample(k_bud, t)
            with scope("select"):
                sel_mask, w_full, algo_state = strategy.select(
                    carry.algo_state, k_sel, avail, k_t, SelectCtx(t=t))
            with scope("cohort"):
                # dispatch the selected cohort into the pending pool
                ids, valid = cohort_ids_from_mask(sel_mask, budget.k_max)
                lat = arrival.latencies(k_arr, t)
                t_f = jnp.asarray(t, jnp.float32)
                new = ArrivalPool(
                    time=jnp.where(valid, t_f + lat[ids], jnp.inf),
                    cid=jnp.where(valid, ids, n).astype(jnp.int32),
                    round=jnp.where(valid, jnp.asarray(t, jnp.int32), 0),
                    valid=valid)
                pool, n_overflow = pool_insert(carry.pool, new)
                # flush: aggregate the first M pending arrivals
                pool, buf_ids, buf_valid, buf_stale = pool_flush(
                    pool, self.buffer_size, t, n)
                weights = staleness_weights(buf_stale, buf_valid,
                                            self.staleness_power,
                                            self.staleness_discount)
                batch = staged_cohort_batch(staged, k_batch, buf_ids,
                                            local_steps, local_batch)
            params, opt_state, m = fed_round(
                carry.params, carry.opt_state, batch, weights,
                jnp.asarray(client_lr, jnp.float32))
            with scope("stream"):
                n_buf = buf_valid.sum().astype(jnp.int32)
                mean_stale = jnp.where(
                    n_buf > 0,
                    (buf_stale * buf_valid).sum() / jnp.maximum(n_buf, 1),
                    0.0).astype(jnp.float32)
                out = AsyncStream(sel_mask=sel_mask, buf_ids=buf_ids,
                                  buf_valid=buf_valid,
                                  buf_staleness=buf_stale,
                                  buf_weights=weights, k_t=k_t,
                                  n_available=avail.sum().astype(jnp.int32),
                                  n_buffered=n_buf, mean_staleness=mean_stale,
                                  n_overflow=n_overflow,
                                  train_loss=m.loss, delta_norm=m.delta_norm)
            return AsyncCarry(key, params, opt_state, algo_state,
                              avail_state, pool), out

        self._chunk = jax.jit(lambda carry, ts:
                              jax.lax.scan(round_step, carry, ts))

        def init_carry(key):
            params = init_params(key)
            return AsyncCarry(key=key, params=params,
                              opt_state=opt.init(params),
                              algo_state=strategy.init(n),
                              avail_state=avail_model.init(),
                              pool=empty_pool(self.pool_slots, n))

        self.init_carry = init_carry

    def chunk(self, carry, ts):
        """Advance one chunk of server steps; returns (carry', AsyncStream)."""
        with jax.profiler.TraceAnnotation("chunk_dispatch", rounds=len(ts)):
            return self._chunk(carry, ts)


# ---------------------------------------------------------------------------
# Cell construction shared by the host and device paths
# ---------------------------------------------------------------------------

def _build_async_cell(scenario, algo_name, *, seed, clients_per_round, beta,
                      server_opt, server_lr, prox_mu, positively_correlated,
                      fed_mode, strategy_kwargs, completion, completion_kwargs,
                      buffer_size, staleness_power, staleness_discount,
                      select_impl="xla"):
    from .runner import build_task    # local import: runner ↔ engine
    sc = get_scenario(scenario)
    algo_name, server_opt, server_lr = resolve_strategy(algo_name, server_opt,
                                                        server_lr)
    entry = get_strategy_entry(algo_name)
    if entry.host_only:
        raise ValueError(
            f"strategy {algo_name!r} is host-only and not supported by the "
            f"buffered/async engine (its per-round host state has no "
            f"arrival-time semantics)")
    if staleness_discount not in STALENESS_DISCOUNTS:
        raise KeyError(f"unknown staleness discount {staleness_discount!r}; "
                       f"known: {sorted(STALENESS_DISCOUNTS)}")
    task, fed, init, loss, acc = build_task(sc.task, seed,
                                            **dict(sc.task_kwargs))
    n = fed.n_clients
    p = fed.p
    m = clients_per_round or task.clients_per_round
    beta = beta if beta is not None else task.beta

    avail_model = sc.build_availability(n, p=p)
    budget = sc.build_budget(default_k=m)
    arrival = sc.build_completion(n, avail_model=avail_model,
                                  override=completion,
                                  override_kwargs=completion_kwargs)
    if not getattr(arrival, "has_latency", False):
        raise ValueError(
            f"aggregation='buffered' needs a latency-capable completion "
            f"process ('always' or 'deadline'), got "
            f"{type(arrival).__name__}: a Bernoulli dropout draw has no "
            f"arrival time to buffer on")
    buffer_size = int(buffer_size) if buffer_size else max(1, m // 2)

    hyper = dict(beta=beta, positively_correlated=positively_correlated,
                 clients_per_round=m, select_impl=select_impl)
    hyper.update(strategy_kwargs or {})
    strategy = make_strategy(algo_name, n, p, **hyper)
    opt = make_optimizer(server_opt, lr=server_lr)
    fed_round = make_fed_round(loss, opt, mode=fed_mode, prox_mu=prox_mu)
    # the cohort of one buffered step is the buffer, not k_max slots
    sampler = CohortSampler(fed, cohort_size=buffer_size,
                            local_steps=task.local_steps,
                            local_batch=task.local_batch, seed=seed)
    ctx = dict(scenario=sc, task=task, n_clients=n, algo_name=algo_name,
               rounds_default=sc.rounds or task.rounds,
               eval_loss=jax.jit(loss), eval_acc=jax.jit(acc),
               test_batch={k: jnp.asarray(v)
                           for k, v in fed.test_batch().items()},
               avail_model=avail_model, budget=budget, strategy=strategy,
               arrival=arrival, opt=opt, init=init,
               fed_round=fed_round, sampler=sampler,
               buffer_size=buffer_size,
               pool_slots=default_pool_slots(buffer_size, budget.k_max))
    return ctx


def _result_arrays(streams, n_real):
    """Stack per-chunk AsyncStream numpy structs into (T, ...) arrays."""
    def cat(name):
        return np.concatenate([getattr(s, name) for s in streams], axis=0)
    sel_history = cat("sel_mask")[:, :n_real]
    buf_ids = cat("buf_ids")
    buf_valid = cat("buf_valid")
    comp_history = np.zeros_like(sel_history)
    t_idx = np.repeat(np.arange(buf_ids.shape[0]), buf_ids.shape[1])
    flat_ids = buf_ids.ravel()
    flat_valid = buf_valid.ravel()
    comp_history[t_idx[flat_valid], flat_ids[flat_valid]] = True
    async_history = dict(
        buf_ids=buf_ids, buf_valid=buf_valid,
        buf_staleness=cat("buf_staleness"), buf_weights=cat("buf_weights"),
        n_buffered=cat("n_buffered"), mean_staleness=cat("mean_staleness"),
        n_overflow=cat("n_overflow"))
    return sel_history, comp_history, async_history


# ---------------------------------------------------------------------------
# Driver: one buffered cell end-to-end (host or device)
# ---------------------------------------------------------------------------

def run_scenario_buffered(scenario: Union[str, Scenario],
                          algo_name: str = "f3ast", *,
                          rounds: Optional[int] = None,
                          server_opt: str = "sgd",
                          server_lr: Optional[float] = 1.0,
                          clients_per_round: Optional[int] = None,
                          beta: Optional[float] = None, seed: int = 0,
                          eval_every: int = 10,
                          chunk_size: Optional[int] = None,
                          ckpt_dir: Optional[str] = None,
                          prox_mu: float = 0.0,
                          positively_correlated: bool = False,
                          metrics_path: Optional[str] = None,
                          fed_mode: str = "parallel",
                          strategy_kwargs=None,
                          completion: Optional[str] = None,
                          completion_kwargs=None,
                          buffer_size: Optional[int] = None,
                          staleness_power: float = 0.5,
                          staleness_discount: str = "polynomial",
                          select_impl: str = "xla",
                          engine: str = "device",
                          algo_label: Optional[str] = None,
                          log_fn=print):
    """Run one buffered-aggregation cell on the named engine.

    ``engine="device"`` runs the compiled :class:`AsyncEngine` scan;
    ``engine="host"`` runs the event-driven reference loop.  Both paths
    produce bit-identical buffer membership, staleness values, and
    aggregation weights for the same seed (``tests/test_engine_async.py``).
    """
    if engine not in ("device", "host"):
        raise ValueError(f"engine must be 'device' or 'host', got {engine!r}")
    ctx = _build_async_cell(
        scenario, algo_name, seed=seed, clients_per_round=clients_per_round,
        beta=beta, server_opt=server_opt, server_lr=server_lr,
        prox_mu=prox_mu, positively_correlated=positively_correlated,
        fed_mode=fed_mode, strategy_kwargs=strategy_kwargs,
        completion=completion, completion_kwargs=completion_kwargs,
        buffer_size=buffer_size, staleness_power=staleness_power,
        staleness_discount=staleness_discount, select_impl=select_impl)
    sc, task = ctx["scenario"], ctx["task"]
    rounds = rounds or ctx["rounds_default"]
    algo_label = algo_label or algo_name
    run = _run_buffered_device if engine == "device" else _run_buffered_host
    result = run(ctx, rounds=rounds, seed=seed, eval_every=eval_every,
                 chunk_size=chunk_size, ckpt_dir=ckpt_dir,
                 metrics_path=metrics_path, staleness_power=staleness_power,
                 staleness_discount=staleness_discount,
                 algo_label=algo_label, log_fn=log_fn)
    result.final_metrics["select_path"] = select_path(select_impl,
                                                      ctx["n_clients"])
    return result


def _open_metrics(metrics_path):
    if not metrics_path:
        return None
    os.makedirs(os.path.dirname(os.path.abspath(metrics_path)),
                exist_ok=True)
    return open(metrics_path, "w")


def _final_rates(strategy, algo_state, n_real):
    r = strategy_rates(strategy, algo_state)
    if r is None:
        return np.full(n_real, np.nan, np.float32)
    return np.asarray(r)[..., :n_real]


def _record(sc, algo_label, t, *, k_t, n_available, n_selected, n_buffered,
            mean_staleness, n_overflow, train_loss, delta_norm):
    """One self-describing JSONL record per server step (async schema:
    the sync fields plus buffer occupancy / staleness / overflow)."""
    return dict(scenario=sc.name, algorithm=algo_label, round=t,
                k_t=int(k_t), n_available=int(n_available),
                n_selected=int(n_selected), n_buffered=int(n_buffered),
                mean_staleness=float(mean_staleness),
                n_overflow=int(n_overflow), train_loss=float(train_loss),
                delta_norm=float(delta_norm))


def _run_buffered_device(ctx, *, rounds, seed, eval_every, chunk_size,
                         ckpt_dir, metrics_path, staleness_power,
                         staleness_discount, algo_label, log_fn):
    from .runner import TrainResult   # local import: runner ↔ engine
    sc, task = ctx["scenario"], ctx["task"]
    engine = AsyncEngine(
        avail_model=ctx["avail_model"], budget=ctx["budget"],
        strategy=ctx["strategy"], staged=ctx["sampler"].stage_device(),
        fed_round=ctx["fed_round"], init_params=ctx["init"], opt=ctx["opt"],
        client_lr=task.client_lr, local_steps=task.local_steps,
        local_batch=task.local_batch, arrival=ctx["arrival"],
        buffer_size=ctx["buffer_size"], staleness_power=staleness_power,
        staleness_discount=staleness_discount,
        pool_slots=ctx["pool_slots"])
    n_real = engine.n_clients
    chunk_size = max(1, min(chunk_size or eval_every, eval_every, rounds))
    carry = engine.init_carry(jax.random.PRNGKey(seed))
    metrics_file = _open_metrics(metrics_path)
    history, streams = [], []
    t_start = time.time()
    t_first_chunk = None
    try:
        for t0 in range(0, rounds, chunk_size):
            t1 = min(t0 + chunk_size, rounds)
            ts = jnp.arange(t0, t1, dtype=jnp.int32)
            # Under REPRO_SANITIZE=1 any stray implicit host<->device
            # transfer inside the compiled chunk raises (core.sanitize).
            with guard_transfers():
                carry, out = engine.chunk(carry, ts)
            out_np = _pull(out)
            if t_first_chunk is None:
                t_first_chunk = time.time()
            streams.append(out_np)
            do_eval = (t1 == rounds
                       or any(t % eval_every == 0 for t in range(t0, t1)))
            if do_eval:
                with jax.profiler.TraceAnnotation("eval"):
                    test_loss = float(ctx["eval_loss"](carry.params,
                                                       ctx["test_batch"]))
                    test_acc = float(ctx["eval_acc"](carry.params,
                                                     ctx["test_batch"]))
                history.append(dict(
                    round=t1 - 1, train_loss=float(out_np.train_loss[-1]),
                    test_loss=test_loss, test_acc=test_acc,
                    n_selected=int(out_np.sel_mask[-1].sum()),
                    n_available=int(out_np.n_available[-1]),
                    n_buffered=int(out_np.n_buffered[-1]),
                    mean_staleness=float(out_np.mean_staleness[-1])))
                log_fn(f"[{sc.name}/{algo_label}] step {t1 - 1:4d} "
                       f"loss={test_loss:.4f} acc={test_acc:.4f} "
                       f"k_t={int(out_np.k_t[-1])} "
                       f"buf={history[-1]['n_buffered']} "
                       f"stale={history[-1]['mean_staleness']:.1f} "
                       f"avail={history[-1]['n_available']}")
            if metrics_file:
                with jax.profiler.TraceAnnotation("metrics_write"):
                    for i, t in enumerate(range(t0, t1)):
                        record = _record(
                            sc, algo_label, t, k_t=out_np.k_t[i],
                            n_available=out_np.n_available[i],
                            n_selected=out_np.sel_mask[i].sum(),
                            n_buffered=out_np.n_buffered[i],
                            mean_staleness=out_np.mean_staleness[i],
                            n_overflow=out_np.n_overflow[i],
                            train_loss=out_np.train_loss[i],
                            delta_norm=out_np.delta_norm[i])
                        if do_eval and t == t1 - 1:
                            record["test_loss"] = test_loss
                            record["test_acc"] = test_acc
                        metrics_file.write(json.dumps(record) + "\n")
                    metrics_file.flush()
            if ckpt_dir:
                with jax.profiler.TraceAnnotation("checkpoint"):
                    save_checkpoint(ckpt_dir, t1,
                                    {"params": carry.params,
                                     "rates": _final_rates(
                                         engine.strategy, carry.algo_state,
                                         n_real)})
    finally:
        if metrics_file:
            metrics_file.close()
    t_end = time.time()
    sel_history, comp_history, async_history = _result_arrays(streams, n_real)
    final = dict(history[-1])
    final["engine"] = "device"
    final["aggregation"] = "buffered"
    final["wall_s"] = t_end - t_start
    steady = rounds - min(chunk_size, rounds)
    if steady > 0 and t_end > t_first_chunk:
        final["steady_rounds_per_s"] = steady / (t_end - t_first_chunk)
    return TrainResult(history=history, final_metrics=final,
                       rates=_final_rates(engine.strategy, carry.algo_state,
                                          n_real),
                       empirical_rates=sel_history.mean(0),
                       sel_history=sel_history, comp_history=comp_history,
                       async_history=async_history)


def _run_buffered_host(ctx, *, rounds, seed, eval_every, chunk_size,
                       ckpt_dir, metrics_path, staleness_power,
                       staleness_discount, algo_label, log_fn):
    """Event-driven reference loop over a sorted pending-arrival list.

    Implements the §7.4 semantics with plain Python data structures —
    a list of (arrival_time, client, dispatch_step, base_w) events kept
    sorted — and is parity-tested bit-for-bit against the compiled pool.
    ``chunk_size`` is accepted for signature symmetry; the host loop has
    no chunking.
    """
    from .runner import TrainResult   # local import: runner ↔ engine
    sc, task = ctx["scenario"], ctx["task"]
    avail_model, budget = ctx["avail_model"], ctx["budget"]
    strategy, arrival = ctx["strategy"], ctx["arrival"]
    sampler, opt = ctx["sampler"], ctx["opt"]
    n = ctx["n_clients"]
    m_buf = ctx["buffer_size"]
    pool_slots = ctx["pool_slots"]
    fed_round = jax.jit(ctx["fed_round"])

    key = jax.random.PRNGKey(seed)
    params = ctx["init"](key)
    opt_state = opt.init(params)
    algo_state = strategy.init(n)
    avail_state = avail_model.init()
    lr_t = jnp.asarray(task.client_lr, jnp.float32)

    pending = []   # [(time, cid, dispatch_step)] kept sorted lexically
    metrics_file = _open_metrics(metrics_path)
    history = []
    sel_history = np.zeros((rounds, n), bool)
    comp_history = np.zeros((rounds, n), bool)
    async_history = dict(
        buf_ids=np.zeros((rounds, m_buf), np.int32),
        buf_valid=np.zeros((rounds, m_buf), bool),
        buf_staleness=np.zeros((rounds, m_buf), np.int32),
        buf_weights=np.zeros((rounds, m_buf), np.float32),
        n_buffered=np.zeros(rounds, np.int32),
        mean_staleness=np.zeros(rounds, np.float32),
        n_overflow=np.zeros(rounds, np.int32))
    t_start = time.time()
    t_first_round = None
    try:
        for t in range(rounds):
            # Split order shared with AsyncEngine.round_step — parity.
            key, k_av, k_sel, k_bud, k_batch = jax.random.split(key, 5)
            k_arr = jax.random.fold_in(k_sel, KEY_FOLD)
            avail_state, avail = avail_model.step(k_av, avail_state, t)
            k_t = budget.sample(k_bud, t)
            sel_mask, w_full, algo_state = strategy.select(
                algo_state, k_sel, avail, k_t, SelectCtx(t=t))
            sel_ids = np.flatnonzero(np.asarray(sel_mask))
            sel_history[t, sel_ids] = True
            # dispatch: one arrival event per selected client
            lat = np.asarray(arrival.latencies(k_arr, t), np.float32)
            t_f = np.float32(t)
            for cid in sel_ids:
                pending.append((float(t_f + lat[cid]), int(cid), t))
            pending.sort()
            n_overflow = max(0, len(pending) - pool_slots)
            del pending[pool_slots:]
            # flush: the first M pending arrivals form the buffer
            buf = pending[:m_buf]
            del pending[:m_buf]
            buf_cids = [e[1] for e in buf]
            stale = np.zeros(m_buf, np.int32)
            bvalid = np.zeros(m_buf, bool)
            for i, (_, cid, t_disp) in enumerate(buf):
                stale[i] = t - t_disp
                bvalid[i] = True
            weights = staleness_weights(stale, bvalid,
                                        staleness_power, staleness_discount)
            batch_np, _, ids_pad = sampler.cohort_batch(
                buf_cids if buf_cids else [n - 1], key=k_batch)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            params, opt_state, metrics = fed_round(params, opt_state, batch,
                                                   weights, lr_t)
            if t == 0:
                jax.block_until_ready(metrics.loss)
                t_first_round = time.time()
            comp_history[t, buf_cids] = True
            async_history["buf_ids"][t] = ids_pad
            async_history["buf_valid"][t] = bvalid
            async_history["buf_staleness"][t] = stale
            async_history["buf_weights"][t] = np.asarray(weights)
            async_history["n_buffered"][t] = len(buf)
            async_history["mean_staleness"][t] = (
                float(stale[bvalid].mean()) if buf else 0.0)
            async_history["n_overflow"][t] = n_overflow

            record = _record(sc, algo_label, t, k_t=int(k_t),
                             n_available=int(np.asarray(avail).sum()),
                             n_selected=len(sel_ids), n_buffered=len(buf),
                             mean_staleness=async_history["mean_staleness"][t],
                             n_overflow=n_overflow,
                             train_loss=float(metrics.loss),
                             delta_norm=float(metrics.delta_norm))
            if t % eval_every == 0 or t == rounds - 1:
                record["test_loss"] = float(ctx["eval_loss"](
                    params, ctx["test_batch"]))
                record["test_acc"] = float(ctx["eval_acc"](
                    params, ctx["test_batch"]))
                history.append(dict(
                    round=t, train_loss=record["train_loss"],
                    test_loss=record["test_loss"],
                    test_acc=record["test_acc"],
                    n_selected=record["n_selected"],
                    n_available=record["n_available"],
                    n_buffered=record["n_buffered"],
                    mean_staleness=record["mean_staleness"]))
                log_fn(f"[{sc.name}/{algo_label}] step {t:4d} "
                       f"loss={record['test_loss']:.4f} "
                       f"acc={record['test_acc']:.4f} k_t={record['k_t']} "
                       f"buf={record['n_buffered']} "
                       f"stale={record['mean_staleness']:.1f} "
                       f"avail={record['n_available']}")
            if metrics_file:
                metrics_file.write(json.dumps(record) + "\n")
                metrics_file.flush()
            if ckpt_dir and (t + 1) % 100 == 0:
                save_checkpoint(ckpt_dir, t + 1,
                                {"params": params,
                                 "rates": _final_rates(strategy, algo_state,
                                                       n)})
    finally:
        if metrics_file:
            metrics_file.close()
    t_end = time.time()
    final = dict(history[-1])
    final["engine"] = "host"
    final["aggregation"] = "buffered"
    final["wall_s"] = t_end - t_start
    if rounds > 1 and t_first_round is not None and t_end > t_first_round:
        final["steady_rounds_per_s"] = (rounds - 1) / (t_end - t_first_round)
    return TrainResult(history=history, final_metrics=final,
                       rates=_final_rates(strategy, algo_state, n),
                       empirical_rates=sel_history.mean(0),
                       sel_history=sel_history, comp_history=comp_history,
                       async_history=async_history)
