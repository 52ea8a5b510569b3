"""Client-sharded round engine: the client dimension N partitioned on a mesh.

The device engine in :mod:`repro.sim.engine` keeps every (N,)-shaped object
— availability state, r_k rates, selection scores, the staged (N, S, ...)
client data — on ONE device, capping N at what a single HBM/host can hold.
This module partitions that client dimension over the ``clients`` axis of a
1-D ``("clients",)`` mesh (``launch.mesh.make_client_mesh``) — or the
leading axis of a 2-D ``("clients", "model")`` mesh
(``launch.mesh.make_fed_mesh``), whose trailing axis then shards each
cohort client's parameters tensor-parallel — and runs the whole chunked
round loop inside ``shard_map``:

* **state** — availability-process state and the staged client arrays live
  sharded over the ``clients`` axis (padded to a multiple of the mesh size;
  padded clients are never available and never selected); the selection
  strategy's own state (e.g. the r_k rate EMA) stays replicated at real-N
  shape — it is O(N) elementwise data, a few hundred KB at N = 100k;
* **selection** — the generic blockwise adapter
  :func:`repro.core.strategies.as_sharded` wraps any registered strategy's
  ``score``/``finalize`` pieces around the distributed top-k in
  :func:`repro.core.selection.sharded_topk_mask` (per-shard top-k_max
  candidates → streaming ppermute merge, or the legacy ``all_gather``,
  per ``topk_impl`` — → global K_t cut with the single-device tie-break)
  — no per-algorithm sharded branches anywhere;
* **cohort** — with staged arrays, each shard contributes the rows it
  owns for the selected cohort (masked gather + ``psum``); with a
  :class:`repro.data.synthetic.SynthTask` the cohort block is synthesized
  on demand from the client ids (``synth_cohort_batch`` — the identical
  keyed generator call the unsharded engine makes, so batches are
  bit-equal, and nothing O(N) is ever resident).  Either way the
  cohort-slot axis is then laid over the mesh so local SGD runs
  data-parallel (``make_fed_round(cohort_axis=...)`` psums the weighted
  delta);
* **completion** — the mid-round dropout draw (``sim/completion.py``)
  happens at full (N,) shape from the replicated derived key, like the
  selection scores, so every shard sees the same completed mask; it is
  drawn once, inside the selection adapter, from the adapter's gathered
  selection mask; the per-shard block streams out next to the selection
  mask and dropped cohort slots are zero-weighted before the psum;
* **masks** — the one full-width mask crossing shards per round (the
  selection mask inside ``as_sharded``; availability is already
  replicated from the full-width step and completion derives from the
  gathered selection mask in place) moves
  bit-packed uint32 words (``core.bitmask.all_gather_bits``), and the
  per-round selection/completion streams leave the compiled loop packed
  as (C, n_pad/32) words — 8× less collective and device→host traffic
  than byte-bools.  Per-shard packing is exact because the staging pad
  quantum keeps every shard block a multiple of 32 clients
  (``data.pipeline.SHARD_PAD_QUANTUM``).

Parity is exact by construction and asserted in
``tests/test_engine_sharded.py``: per-round PRNG keys are replicated and
split in the same order as the single-device engine and the host loop, and
every random field (availability draws, selection tie-breaks / Gumbel
scores, minibatch indices) is drawn at the full (N,) shape from the same
key — each shard then slices its own block — so the same seed yields
bit-identical availability masks, selection masks, K_t draws, and r_k
trajectories, and losses matching to float tolerance (the only divergence
is the ``psum`` reduction order in the delta aggregation).

O(N) elementwise fields being recomputed replicated is deliberate: they are
a few hundred KB at N = 100k, while the objects that actually scale with N
— staged client data, rates, availability state, and the top-k sort — are
sharded or reduced to per-shard candidates.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.bitmask import pack_bits
from ..core.selection import sharded_cohort_ids_from_mask
from ..core.spans import collective_scope, scope
from ..core.strategies import SelectCtx, as_sharded
from ..data.pipeline import SHARD_PAD_QUANTUM, synth_cohort_batch
from ..data.synthetic import SynthTask
from ..sharding.rules import (model_specs, pad_client_dim, state_specs_like,
                              to_named_shardings)
from ..core.keys import COMPLETION as KEY_FOLD
from .engine import EngineCarry, RoundStream, _staged_nbytes

__all__ = ["ShardedEngine", "resolve_client_mesh"]


def _selection_comm_bytes(*, d: int, nl: int, k: int, topk_impl: str,
                          gathers: int = 1) -> int:
    """Analytic per-round selection traffic, bytes received per shard.

    Counts the collectives selection is made of — the top-k candidate
    reduction ((f32 score, i32 gid) pairs), the cohort-id reduction (i32
    ids, same schedule), and ``gathers`` full-width mask gathers — under
    the packed-uint32 mask wire format.  ``gathers`` is 1 on the fast
    path (only the selection mask moves; availability is either stepped
    blockwise or already replicated, and the completed mask is derived
    from the gathered selection mask in place), 2 when the strategy has
    no blockwise score and the availability mask must be reassembled for
    it.  Cohort-batch / delta psums are model traffic, not selection, and
    are excluded.  This is the ``selection_comm_bytes_per_round`` metric
    the drivers surface; the benchmark's bytes-moved column and DESIGN.md
    §7.2 derive from the same formulas.
    """
    if d == 1:
        return 0
    kk = min(k, nl)

    def stream_items(cap: int) -> int:
        if d & (d - 1) == 0:            # butterfly: send current list/stage
            total, length = 0, kk
            for _ in range(d.bit_length() - 1):
                total += length
                length = min(cap, 2 * length)
            return total
        return (d - 1) * kk             # ring: fixed kk-buffer, d-1 hops
    items = stream_items(k) if topk_impl == "stream" else (d - 1) * kk
    mask_bytes = gathers * (d - 1) * (nl // 8 if nl % 32 == 0 else nl)
    return items * 8 + items * 4 + mask_bytes


def resolve_client_mesh(mesh, axis: str = "clients",
                        model_axis: str = "model") -> Mesh:
    """Accept a Mesh, a shard count (``<= 0`` → all devices), a 1- or 2-D
    ``mesh_shape`` tuple (``(c,)`` / ``(c, m)``, 0 = fill), or None."""
    if mesh is None or isinstance(mesh, Mesh):
        if isinstance(mesh, Mesh) and axis not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
        return mesh
    from ..launch.mesh import make_fed_mesh
    if isinstance(mesh, int):
        mesh = (max(mesh, 0),)      # legacy shard count: <= 0 → all devices
    return make_fed_mesh(tuple(mesh), axis_names=(axis, model_axis))


class ShardedEngine:
    """Drop-in for :class:`repro.sim.engine.DeviceEngine` on a client mesh.

    Same driver surface (``init_carry`` / ``set_r0`` / ``chunk`` / ``k_max``
    / ``n_clients``); ``chunk`` compiles one ``shard_map``-wrapped
    ``lax.scan`` over the round chunk.  ``staged`` is either a
    :class:`~repro.data.pipeline.StagedData` from ``CohortSampler.
    stage_device(mesh=...)`` / ``stage_client_arrays`` (client dimension
    already padded and sharded) or a :class:`~repro.data.synthetic.
    SynthTask` — then no client data is resident at all and cohort
    batches are synthesized on demand inside the compiled loop, which is
    what makes N = 1e6–1e7 rounds fit.  ``topk_impl`` picks the
    distributed top-k reduction (``core.selection.TOPK_IMPLS``).

    ``model_axis``: optional second mesh axis (``make_fed_mesh((c, m))``)
    carrying a tensor-parallel split of the stored params and optimizer
    state (per-leaf layout from ``sharding.rules.model_specs``).  All
    client-side state and collectives name only the ``clients`` axis, so
    every model shard computes the identical selection masks / r_k / K_t
    streams; ``fed_round`` must be built with the matching
    ``model_axis``/``param_specs`` (see ``make_fed_round``).
    """

    def __init__(self, *, mesh: Mesh, axis: str = "clients", avail_model,
                 budget, strategy, staged, fed_round, init_params, opt,
                 client_lr, local_steps, local_batch, n_clients: int,
                 completion=None, topk_impl: str = "stream",
                 model_axis: Optional[str] = None):
        self.mesh, self.axis = mesh, axis
        self.model_axis = model_axis
        if model_axis is not None:
            if model_axis == axis:
                raise ValueError(f"model_axis {model_axis!r} collides with "
                                 f"the client axis")
            if model_axis not in mesh.axis_names:
                raise ValueError(f"mesh {mesh.axis_names} has no "
                                 f"{model_axis!r} axis; build it with "
                                 f"launch.mesh.make_fed_mesh((c, m))")
        self.strategy = strategy
        self.completion = completion
        trivial = completion is None or completion.trivial
        self.n_clients = int(n_clients)
        self.k_max = budget.k_max
        self._staged = staged
        self.topk_impl = topk_impl
        synth = isinstance(staged, SynthTask)
        self._synth = synth
        n_shards = mesh.shape[axis]
        if synth:
            assert staged.n_clients == n_clients, (staged.n_clients,
                                                   n_clients)
            quantum = n_shards * SHARD_PAD_QUANTUM
            n_pad = -(-n_clients // quantum) * quantum
        else:
            n_pad = int(staged.counts.shape[0])
        assert n_pad % n_shards == 0 and n_pad >= n_clients, \
            (n_pad, n_shards, n_clients)
        nl = n_pad // n_shards
        assert nl % SHARD_PAD_QUANTUM == 0, (
            f"per-shard block {nl} not a multiple of {SHARD_PAD_QUANTUM}: "
            f"stage through data.pipeline.stage_client_arrays so packed "
            f"mask streaming lines up with shard boundaries")
        k = budget.k_max
        self.n_staged_bytes = _staged_nbytes(staged)
        k_pad = -(-k // n_shards) * n_shards
        kb = k_pad // n_shards
        n = self.n_clients

        # which availability-state leaves carry the client dimension
        avail0 = avail_model.init()
        flags = jax.tree.map(
            lambda leaf: getattr(leaf, "ndim", 0) >= 1
            and leaf.shape[0] == n, avail0)
        self._avail_flags = flags
        # blockwise availability: models exposing step_block (and carrying
        # no (N,)-shaped state) step each shard's slice directly — O(nl)
        # per shard, bitwise-identical to slicing the full-width step
        block_avail = (hasattr(avail_model, "step_block")
                       and not any(jax.tree.leaves(flags)))
        # the availability mask is re-gathered only when a blockwise step
        # left no replicated copy AND the strategy's score needs full width
        gathers = 1 + (1 if block_avail and strategy.score_block is None
                       else 0)
        self.selection_comm_bytes_per_round = _selection_comm_bytes(
            d=n_shards, nl=nl, k=k, topk_impl=topk_impl, gathers=gathers)

        def gather_state(state_blk):
            with collective_scope(axis):
                return jax.tree.map(
                    lambda leaf, f: jax.lax.all_gather(leaf, axis,
                                                       tiled=True)[:n]
                    if f else leaf, state_blk, flags)

        def complete_draw(key, t, sel_mask):
            with scope("complete"):
                return completion.sample(key, t, sel_mask)

        def scatter_state(state_full, off):
            return jax.tree.map(
                lambda leaf, f: jax.lax.dynamic_slice_in_dim(
                    pad_client_dim(leaf, n_pad), off, nl) if f else leaf,
                state_full, flags)

        slot_mask = (jnp.arange(k_pad) < k).astype(jnp.float32)
        e, b = local_steps, local_batch
        # generic blockwise selection: any strategy with a score/finalize
        # decomposition runs here without engine-specific code
        select_blk = as_sharded(strategy, axis=axis, k_max=k, n_pad=n_pad,
                                topk_impl=topk_impl)

        def round_step(carry, t, k_cap, arrays, counts):
            # Same split order as the host loop / device engine — parity.
            # The completion key is derived (fold_in off k_sel), replicated
            # across shards, and the completion draw happens at full (N,)
            # shape — bit-identical masks on every shard and engine.
            key, k_av, k_sel, k_bud, k_batch = jax.random.split(carry.key, 5)
            k_comp = jax.random.fold_in(k_sel, KEY_FOLD)
            i = jax.lax.axis_index(axis)
            off = i * nl

            with scope("avail"):
                if block_avail:
                    # blockwise: each shard steps only its slice (O(nl), no
                    # (N,) intermediate, non-empty fix via tiny collectives)
                    avail_state, avail_blk = avail_model.step_block(
                        k_av, carry.avail_state, t, off=off, n_local=nl,
                        axis=axis)
                    avail_full = None
                    n_blk = avail_blk.sum().astype(jnp.int32)
                    with collective_scope(axis):
                        n_avail = jax.lax.psum(n_blk, axis)
                else:
                    # availability: full-width replicated step, sharded state
                    full_state = gather_state(carry.avail_state)
                    new_full, avail_full = avail_model.step(k_av, full_state,
                                                            t)
                    avail_state = scatter_state(new_full, off)
                    avail_blk = jax.lax.dynamic_slice_in_dim(
                        pad_client_dim(avail_full, n_pad), off, nl)
                    n_avail = avail_full.sum().astype(jnp.int32)

            with scope("budget"):
                k_t = jnp.minimum(budget.sample(k_bud, t),
                                  jnp.asarray(k_cap, jnp.int32))
            complete_fn = (None if trivial else
                           lambda m: complete_draw(k_comp, t, m))
            # avail_full is already replicated from the full-width step, so
            # the adapter skips its gather; completed_full comes back from
            # the adapter's own mask gather + completion draw — no second
            # gather, no re-draw
            with scope("select"):
                mask_blk, w_blk, algo_state, completed_full = select_blk(
                    carry.algo_state, k_sel, avail_blk, k_t,
                    SelectCtx(t=t, complete=complete_fn),
                    avail_full=avail_full)
            if trivial:
                completed_blk = mask_blk
            else:
                completed_blk = jax.lax.dynamic_slice_in_dim(
                    pad_client_dim(completed_full, n_pad), off, nl)

            with scope("cohort"):
                lb, lw, lm = cohort(k_batch, mask_blk, w_blk,
                                    completed_full, i, off, arrays, counts)
            params, opt_state, m = fed_round(
                carry.params, carry.opt_state, lb, lw,
                jnp.asarray(client_lr, jnp.float32), lm)

            # masks stream packed per shard (nl % 32 == 0 ⇒ concatenated
            # shard words == packing the full mask); the host unpacks once
            with scope("stream"):
                out = RoundStream(sel_mask=pack_bits(mask_blk),
                                  completed=pack_bits(completed_blk),
                                  k_t=k_t,
                                  n_available=n_avail,
                                  train_loss=m.loss,
                                  delta_norm=m.delta_norm)
            return EngineCarry(key, params, opt_state, algo_state,
                               avail_state), out

        def cohort(k_batch, mask_blk, w_blk, completed_full, i, off, arrays,
                   counts):
            """This shard's slice of the cohort: its batch rows, weights
            and slot mask."""
            ids, valid = sharded_cohort_ids_from_mask(mask_blk, k, axis, n,
                                                      method=topk_impl)
            if k_pad > k:           # shard-count padding: zero-weight repeats
                ids_p = jnp.concatenate(
                    [ids, jnp.broadcast_to(ids[0], (k_pad - k,))])
                valid_p = jnp.concatenate(
                    [valid, jnp.zeros((k_pad - k,), bool)])
            else:
                ids_p, valid_p = ids, valid

            # cohort weights: each slot's value lives on its owner shard
            in_range = (ids_p >= off) & (ids_p < off + nl)
            loc = jnp.where(in_range, ids_p - off, 0)
            w_own = jnp.where(in_range, w_blk[loc], 0.0)
            with collective_scope(axis):
                w_sel = jax.lax.psum(w_own, axis) * valid_p
            if not trivial:
                # dropped slots contribute nothing even if the strategy's
                # finalize ignored the completion hook (replicated mask,
                # ids_p are clamped < n)
                w_sel = w_sel * completed_full[ids_p]

            if synth:
                # on-demand cohort: every shard makes the identical call
                # the unsharded engine makes — same key, same (k,) ids,
                # same vmap width — so the block is bit-equal and
                # replicated with zero resident client data and no psum
                batch = synth_cohort_batch(staged, k_batch, ids,
                                           local_steps, local_batch)
                if k_pad > k:   # shard-count padding: zero rows, zero weight
                    batch = {name: jnp.concatenate(
                        [v, jnp.zeros((k_pad - k,) + v.shape[1:], v.dtype)])
                        for name, v in batch.items()}
            else:
                # minibatch indices: the same (K, E, B) draw as the
                # unsharded engine; padded slots reuse index 0, zero weight
                idx = jax.random.randint(k_batch, (k, e, b), 0,
                                         counts[ids][:, None, None])
                if k_pad > k:
                    idx = jnp.concatenate(
                        [idx, jnp.zeros((k_pad - k, e, b), idx.dtype)])

                # sharded cohort gather: owners contribute, psum assembles
                batch = {}
                for name, arr in arrays.items():
                    rows = arr[loc[:, None, None], idx]
                    keep = in_range.reshape((k_pad,) + (1,) * (rows.ndim - 1))
                    own = jnp.where(keep, rows, 0)
                    with collective_scope(axis):
                        batch[name] = jax.lax.psum(own, axis)

            # cohort-slot axis onto the mesh: each shard trains its slice
            lb = {name: jax.lax.dynamic_slice_in_dim(v, i * kb, kb)
                  for name, v in batch.items()}
            lw = jax.lax.dynamic_slice_in_dim(w_sel, i * kb, kb)
            lm = jax.lax.dynamic_slice_in_dim(slot_mask, i * kb, kb)
            return lb, lw, lm

        def chunk_body(carry, ts, k_cap, arrays=None, counts=None):
            return jax.lax.scan(
                lambda c, t: round_step(c, t, k_cap, arrays, counts),
                carry, ts)

        # spec trees (structure known from shape-only evaluation).  The
        # strategy state is replicated (real-N shape on every shard): the
        # generic adapter computes it full-width, identically per shard.
        params_s = jax.eval_shape(init_params, jax.random.PRNGKey(0))
        opt_s = jax.eval_shape(opt.init, params_s)
        algo_s = jax.eval_shape(lambda: strategy.init(self.n_clients))
        if model_axis is None:
            p_specs = jax.tree.map(lambda _: P(), params_s)
            o_specs = jax.tree.map(lambda _: P(), opt_s)
        else:
            # stored params / optimizer state shard over the model axis
            # (per-leaf layout from the rule engine); fed_round must have
            # been built with the same model_axis + param_specs
            p_specs = model_specs(params_s, mesh, model_axis=model_axis)
            o_specs = state_specs_like(opt_s, params_s, p_specs)
        self.param_specs = p_specs
        carry_specs = EngineCarry(
            key=P(),
            params=p_specs,
            opt_state=o_specs,
            algo_state=jax.tree.map(lambda _: P(), algo_s),
            avail_state=jax.tree.map(lambda f: P(axis) if f else P(), flags),
        )
        stream_specs = RoundStream(sel_mask=P(None, axis),
                                   completed=P(None, axis), k_t=P(),
                                   n_available=P(), train_loss=P(),
                                   delta_norm=P())
        self._carry_shardings = to_named_shardings(carry_specs, mesh)
        if synth:
            in_specs = (carry_specs, P(), P())
        else:
            staged_specs = jax.tree.map(lambda _: P(axis), staged.arrays)
            in_specs = (carry_specs, P(), P(), staged_specs, P())
        self._chunk = jax.jit(jax.shard_map(
            chunk_body, mesh=mesh, in_specs=in_specs,
            out_specs=(carry_specs, stream_specs), check_vma=False))

        def _make_init(r0):
            def init_carry(key):
                params = init_params(key)
                carry = EngineCarry(
                    key=key, params=params, opt_state=opt.init(params),
                    algo_state=strategy.init(self.n_clients, r0=r0),
                    avail_state=jax.tree.map(
                        lambda leaf, f: pad_client_dim(leaf, n_pad)
                        if f else jnp.asarray(leaf),
                        avail_model.init(), flags))
                return jax.device_put(carry, self._carry_shardings)
            return init_carry

        self._make_init = _make_init
        self.init_carry = _make_init(None)
        # Mesh-replicated default cap, staged at build time: drivers call
        # chunk() inside the sanitizer transfer guard (core.sanitize), so
        # the default must not be a fresh host->device (or resharding)
        # transfer per chunk.
        self._k_max_dev = jax.device_put(
            jnp.asarray(self.k_max, jnp.int32),
            to_named_shardings(P(), mesh))

    def set_r0(self, r0: float) -> None:
        """Pin the rate-EMA initialization (runner uses the calibrated M/N)."""
        self.init_carry = self._make_init(r0)

    def chunk(self, carry, ts, k_cap: Optional[int] = None):
        """Advance one chunk of rounds; returns (carry', RoundStream)."""
        if k_cap is None:
            k_cap = self._k_max_dev
        else:
            k_cap = jnp.asarray(k_cap, jnp.int32)
        staged = (() if self._synth
                  else (self._staged.arrays, self._staged.counts))
        with jax.profiler.TraceAnnotation("chunk_dispatch", rounds=len(ts)):
            return self._chunk(carry, ts, k_cap, *staged)
