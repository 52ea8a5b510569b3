"""The jitted federated round.

A round (paper Algorithm 1 lines 6-10) takes the global model w̄^t, runs E
local CLIENTOPT (SGD) steps for every client in the cohort, aggregates the
weighted deltas Δ^{t+1} = Σ_k w_k v_k, and applies SERVEROPT.

The round function is *algorithm-agnostic*: the aggregation weights (K,) are
computed outside (unbiased p_k/r_k for F3AST, normalized p_k for FedAvg, ...)
so the same compiled program serves every algorithm.

Two cohort execution modes (see DESIGN.md §4):

* ``parallel``   — cohort axis is vmapped; params are replicated over the
                   data mesh axes and each shard trains its slice of the
                   cohort.  Memory ≈ K/shards local model copies.
* ``sequential`` — ``lax.scan`` over the cohort; params stay FSDP-sharded and
                   every client's local batch is data-parallel across the
                   whole mesh; the weighted delta accumulates in a sharded
                   f32 buffer.  Memory ≈ 3 sharded model copies, regardless
                   of cohort size.  This is the only feasible mode for
                   100B+ client models.

Batch layout: every leaf of ``cohort_batch`` has shape (K, E, B, ...) —
cohort × local-steps × per-step minibatch.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .aggregation import (streaming_aggregate_add, streaming_aggregate_init,
                          weighted_aggregate)
from .spans import collective_scope, scope
from ..optim.optimizers import Optimizer, apply_updates


class RoundMetrics(NamedTuple):
    loss: jnp.ndarray          # mean local loss over cohort & local steps
    delta_norm: jnp.ndarray    # ||Delta||_2
    grad_norm: jnp.ndarray     # mean per-step grad norm


def _constrain(tree, shardings):
    """Optional sharding constraint (FSDP: keep loop-carried local params and
    accumulators sharded like the global params — without this, XLA keeps the
    scan carry fully replicated and a 314B 'client' materializes unsharded)."""
    if shardings is None:
        return tree
    return jax.lax.with_sharding_constraint(tree, shardings)


def _local_sgd(loss_fn: Callable, params, client_batch, lr, remat: bool,
               shardings=None, prox_mu: float = 0.0):
    """E local SGD steps for one client; returns (v_k, mean_loss, mean_gnorm).

    ``client_batch`` leaves have shape (E, B, ...): one minibatch per local
    step (the paper's CLIENTOPT with E epochs/steps of SGD).

    ``prox_mu > 0`` adds the FedProx proximal term mu/2 ||w - w̄||² to the
    local objective (gradient added in closed form — no extra memory).  The
    paper (§3.2 "Beyond FEDAVG") notes F3AST composes with FedProx; this is
    that composition.
    """
    lf = jax.checkpoint(loss_fn) if remat else loss_fn
    vg = jax.value_and_grad(lf)

    def step(w, batch):
        loss, g = vg(w, batch)
        if prox_mu > 0.0:
            g = jax.tree.map(lambda g_, w_, w0: g_ + prox_mu * (w_ - w0).astype(g_.dtype),
                             g, w, params)
        g = _constrain(g, shardings)
        # per-leaf self-dot in native dtype, accumulate in f32 — avoids
        # materializing f32 copies of every gradient leaf
        gnorm = jnp.sqrt(sum(jnp.sum(x * x).astype(jnp.float32)
                             for x in jax.tree.leaves(g)))
        w = jax.tree.map(lambda p_, g_: (p_ - lr * g_.astype(p_.dtype)).astype(p_.dtype), w, g)
        return _constrain(w, shardings), (loss, gnorm)

    w_end, (losses, gnorms) = jax.lax.scan(step, params, client_batch)
    v_k = jax.tree.map(lambda a, b: (a - b).astype(a.dtype), w_end, params)
    return _constrain(v_k, shardings), losses.mean(), gnorms.mean()


def make_fed_round(loss_fn: Callable, server_opt: Optimizer, *,
                   mode: str = "parallel", remat: bool = False,
                   param_shardings=None, acc_dtype=jnp.float32,
                   prox_mu: float = 0.0, cohort_axis: str = None,
                   cohort_slots: int = None, model_axis: str = None,
                   param_specs=None):
    """Build the jittable round function.

    fed_round(params, opt_state, cohort_batch, weights, client_lr)
        -> (params, opt_state, RoundMetrics)

    ``param_shardings``: optional pytree of NamedShardings matching params —
    pins the sequential-mode scan carries (local params, grads, delta
    accumulator) to the FSDP layout.

    ``cohort_axis``: mesh axis name for the client-sharded engine.  When
    set, the returned function runs *inside* ``shard_map``: it takes this
    shard's slice of the cohort (batch, weights, plus a ``slot_mask`` arg
    flagging which local slots belong to the real K-slot cohort vs. the
    shard-count padding), trains it data-parallel, and ``psum``s the
    weighted delta and metrics across shards.  ``cohort_slots`` is the real
    cohort size K the loss/grad-norm means are normalized by, matching the
    single-device ``losses.mean()`` over K slots.

    ``model_axis`` (with ``cohort_axis``): second mesh axis carrying a
    tensor-parallel split of the *stored* params and optimizer state,
    whose per-leaf layout is ``param_specs`` (a P-tree from
    ``sharding.rules.model_specs``).  The round all-gathers each sharded
    leaf over ``model_axis`` (tiled, so the full array is reconstructed
    bit-exactly), trains the local cohort slice at full width — every
    model shard computes the identical replicated result — then slices
    its own block back out of the weighted delta before the ``psum`` over
    ``cohort_axis`` (slice and psum commute elementwise, so the stored
    blocks stay bitwise slices of the 1-D layout), and applies the
    elementwise server update blockwise.  Only the delta-norm needs an
    extra ``psum`` over ``model_axis`` (partial sums of squares).
    """
    assert mode in ("parallel", "sequential"), mode

    if cohort_axis is not None:
        assert mode == "parallel", "sharded cohort execution is parallel-mode"
        assert cohort_slots is not None, "cohort_axis needs cohort_slots=K"
        if model_axis is not None and param_specs is None:
            raise ValueError("model_axis needs param_specs (a P-tree from "
                             "sharding.rules.model_specs)")

        def _model_dim(spec):
            for i, entry in enumerate(spec):
                if entry is None:
                    continue
                names = (entry,) if isinstance(entry, str) else tuple(entry)
                if model_axis in names:
                    return i
            return None

        def _gather_full(leaf, spec):
            d = _model_dim(spec)
            if d is None:
                return leaf
            return jax.lax.all_gather(leaf, model_axis, axis=d, tiled=True)

        def _slice_block(full, blk_like, spec):
            d = _model_dim(spec)
            if d is None:
                return full
            blk = blk_like.shape[d]
            return jax.lax.dynamic_slice_in_dim(
                full, jax.lax.axis_index(model_axis) * blk, blk, axis=d)

        def _reduce_block(full, blk_like, spec):
            blk = _slice_block(full, blk_like, spec)
            with collective_scope(cohort_axis):
                return jax.lax.psum(blk, cohort_axis)

        def fed_round_sharded(params, opt_state, cohort_batch, weights,
                              client_lr, slot_mask):
            if model_axis is None:
                p_full = params
            else:
                with collective_scope(model_axis):
                    p_full = jax.tree.map(_gather_full, params, param_specs)
            with scope("local_sgd"):
                deltas, losses, gnorms = jax.vmap(
                    lambda b: _local_sgd(loss_fn, p_full, b, client_lr,
                                         remat, prox_mu=prox_mu)
                )(cohort_batch)
            with scope("aggregate"):
                loss_sum = (losses * slot_mask).sum()
                with collective_scope(cohort_axis):
                    loss = jax.lax.psum(loss_sum, cohort_axis) / cohort_slots
                gnorm_sum = (gnorms * slot_mask).sum()
                with collective_scope(cohort_axis):
                    gnorm = jax.lax.psum(gnorm_sum,
                                         cohort_axis) / cohort_slots
                delta_full = weighted_aggregate(deltas, weights)
                if model_axis is None:
                    with collective_scope(cohort_axis):
                        delta = jax.lax.psum(delta_full, cohort_axis)
                    dnorm = jnp.sqrt(sum(jnp.sum(x * x).astype(jnp.float32)
                                         for x in jax.tree.leaves(delta)))
                else:
                    delta = jax.tree.map(_reduce_block, delta_full, params,
                                         param_specs)
                    # per-block partial sums of squares; replicated leaves
                    # are held on every model shard and must be counted once
                    d_leaves = jax.tree.leaves(delta)
                    d_specs = jax.tree.structure(delta).flatten_up_to(
                        param_specs)
                    sq_sharded = sum(
                        (jnp.sum(x * x).astype(jnp.float32)
                         for x, s in zip(d_leaves, d_specs)
                         if _model_dim(s) is not None),
                        jnp.zeros((), jnp.float32))
                    sq_repl = sum(
                        (jnp.sum(x * x).astype(jnp.float32)
                         for x, s in zip(d_leaves, d_specs)
                         if _model_dim(s) is None),
                        jnp.zeros((), jnp.float32))
                    with collective_scope(model_axis):
                        sq_sharded = jax.lax.psum(sq_sharded, model_axis)
                    dnorm = jnp.sqrt(sq_repl + sq_sharded)
            with scope("server_update"):
                updates, opt_state = server_opt.update(delta, opt_state,
                                                       params)
                params = apply_updates(params, updates)
            return params, opt_state, RoundMetrics(loss=loss,
                                                   delta_norm=dnorm,
                                                   grad_norm=gnorm)

        return fed_round_sharded

    def fed_round(params, opt_state, cohort_batch, weights, client_lr):
        if mode == "parallel":
            with scope("local_sgd"):
                deltas, losses, gnorms = jax.vmap(
                    lambda b: _local_sgd(loss_fn, params, b, client_lr,
                                         remat, prox_mu=prox_mu)
                )(cohort_batch)
            with scope("aggregate"):
                delta = weighted_aggregate(deltas, weights)
        else:
            with scope("aggregate"):
                acc0 = streaming_aggregate_init(params, acc_dtype)

            def body(acc, xs):
                batch_k, w_k = xs
                with scope("local_sgd"):
                    v_k, loss_k, gnorm_k = _local_sgd(
                        loss_fn, params, batch_k, client_lr, remat,
                        shardings=param_shardings, prox_mu=prox_mu)
                with scope("aggregate"):
                    acc = streaming_aggregate_add(acc, v_k, w_k)
                    acc = _constrain(acc, param_shardings)
                return acc, (loss_k, gnorm_k)

            acc, (losses, gnorms) = jax.lax.scan(body, acc0, (cohort_batch, weights))
            with scope("aggregate"):
                delta = jax.tree.map(lambda a, p_: a.astype(p_.dtype), acc,
                                     params)

        with scope("aggregate"):
            loss = losses.mean()
            gnorm = gnorms.mean()
            # self-dot per leaf WITHOUT reshaping: vdot flattens to 1-D, and
            # a reshape of a sharded tensor cannot preserve its sharding —
            # XLA all-gathers the full tree (observed: +60 GB/device on an
            # 8B model)
            dnorm = jnp.sqrt(sum(jnp.sum(x * x).astype(jnp.float32)
                                 for x in jax.tree.leaves(delta)))
        with scope("server_update"):
            updates, opt_state = server_opt.update(delta, opt_state, params)
            params = apply_updates(params, updates)
        return params, opt_state, RoundMetrics(loss=loss, delta_norm=dnorm,
                                               grad_norm=gnorm)

    return fed_round
