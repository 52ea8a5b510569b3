"""Client-selection policies (paper Alg. 1 line 4 and its baselines).

All selectors are jit-safe pure functions
    (key, avail_mask (N,), k_budget scalar, ...) -> selection mask (N,) bool
with |S| = min(k_budget, |available|): the paper's constraint that the
cohort S_t ⊆ C_t (the available set) and |S_t| ≤ K_t (the round's
time-varying communication budget, §2).

Implemented policies
  * ``f3ast_select``   — Algorithm 1 line 4: greedy top-K_t available clients
                         by marginal utility −∇H(r) (exact maximizer of the
                         additive set objective, Eq. 4).
  * ``fedavg_select``  — availability-agnostic baseline (paper §4, Li et
                         al. scheme II): sample K_t clients from the
                         available set without replacement with probability
                         ∝ p_k (Gumbel top-k).
  * ``uniform_select`` — uniform without replacement over the available set.
  * ``poc_select``     — Power-of-Choice (Cho et al.): sample d candidates
                         ∝ p_k from the available set, then keep the M with
                         the highest local loss.
  * ``fixed_policy_select`` — Algorithm 2: greedy w.r.t. a *fixed* target
                         rate r (static configuration-dependent policy).

Tie-break contract (``(score, id)``): every top-k cut in the repo — the
argsort path (:func:`_topk_mask`), the distributed path
(:func:`sharded_topk_mask`), and the fused Pallas kernel
(``repro.kernels.fed_select``) — resolves equal scores to the LOWER client
id, i.e. ranks by the pair (−score, id).  This is what makes host, device,
sharded, and kernel selection masks bit-identical for the same inputs
(DESIGN.md §3.1); any new cut implementation must preserve it or the
cross-engine parity matrix fails.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .hfun import marginal_utility
from .spans import collective_scope, scope

# Score sentinel for unavailable clients — low enough that no real score
# (utility, Gumbel, uniform) reaches it, so unavailable clients rank last.
# ``kernels.ref.SELECT_NEG`` must stay equal to it.
_NEG = -1e30


def _topk_mask(scores: jnp.ndarray, avail: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask of the top-min(k, |avail|) available entries by score.

    The reference spelling of the line-4 cut ``S_t ∈ argmax_{S ⊆ C_t,
    |S| ≤ K_t} score·1_S``: rank every client by a stable descending
    argsort of the availability-masked scores and keep ranks ``< k_eff``.
    Stability of the argsort is load-bearing — it yields the ``(score,
    id)`` tie-break of the module contract.  ``repro.kernels.fed_select``
    reformulates this exact cut as a sort-free-of-argsort threshold pass
    (bit-identical, ``tests/test_kernels_select.py``); strategies switch
    between the two via ``RunSpec.select_impl``.
    """
    n = scores.shape[0]
    with scope("topk"):
        masked = jnp.where(avail, scores, _NEG)
        # Rank positions by score (descending); position i selected iff its
        # rank < k and it is available.  Stable w.r.t. ties via argsort.
        order = jnp.argsort(-masked)            # indices, best first
        ranks = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        k_eff = jnp.minimum(k.astype(jnp.int32),
                            avail.sum().astype(jnp.int32))
        return (ranks < k_eff) & avail


def f3ast_select(avail: jnp.ndarray, k: jnp.ndarray, p: jnp.ndarray,
                 r: jnp.ndarray, positively_correlated: bool = False,
                 key: jax.Array | None = None) -> jnp.ndarray:
    """F3AST greedy selection: S_t ∈ argmax_{S∈C_t} −∇H(r(t))·1_S.

    Algorithm 1 line 4.  Because the surrogate objective H(r) (Eq. 3) is
    separable across clients, the argmax over all ≤K_t-subsets of C_t is
    exactly the top-K_t available clients by the marginal utility
    −∂H/∂r_k (Eq. 4, ``hfun.marginal_utility``) — greedy is optimal, no
    combinatorial search.  ``r`` is the tracked rate EMA r(t−1)
    (``rates.update_rates`` advances it AFTER selection, line 5).
    """
    util = marginal_utility(r, p, positively_correlated)
    if key is not None:
        # Infinitesimal random tie-break so identical utilities (e.g. at
        # initialization with uniform r) do not deterministically favor
        # low-index clients.
        util = util * (1.0 + 1e-6 * jax.random.uniform(key, util.shape))
    return _topk_mask(util, avail, k)


def fixed_policy_select(avail: jnp.ndarray, k: jnp.ndarray, p: jnp.ndarray,
                        r_target: jnp.ndarray,
                        positively_correlated: bool = False) -> jnp.ndarray:
    """Fixed-policy F3AST (Algorithm 2): greedy w.r.t. a frozen rate.

    Identical to Alg. 1 line 4 except the utility is evaluated at a
    *static* target rate r (configuration-dependent, computed offline)
    instead of the tracked EMA — the paper's deployment mode when the
    availability statistics are known and per-round adaptation is not
    wanted.
    """
    util = marginal_utility(r_target, p, positively_correlated)
    return _topk_mask(util, avail, k)


def fedavg_select(key: jax.Array, avail: jnp.ndarray, k: jnp.ndarray,
                  p: jnp.ndarray,
                  topk: Optional[Callable] = None) -> jnp.ndarray:
    """Sample min(k,|avail|) available clients w/o replacement, prob ∝ p_k.

    Uses the Gumbel top-k trick: adding i.i.d. Gumbel noise to log p and
    taking the top-k is exactly sequential sampling without replacement with
    probabilities proportional to p.  The paper's FedAvg baseline (§4):
    selection ignores r, so under intermittent availability the resulting
    update is biased toward frequently-available clients (the bias Eq. 6's
    p_k/r_k reweighting removes).

    ``topk`` optionally swaps the cut implementation (``RunSpec.
    select_impl="pallas"`` passes ``kernels.fed_select.fed_select_mask``);
    defaults to :func:`_topk_mask` — same mask either way.
    """
    g = jax.random.gumbel(key, p.shape)
    scores = jnp.log(jnp.maximum(p, 1e-12)) + g
    return (topk or _topk_mask)(scores, avail, k)


def uniform_select(key: jax.Array, avail: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Uniform without replacement over the available set: i.i.d. uniform
    scores + top-k is a uniformly random ≤k-subset of C_t (the
    availability-aware 'uniform' baseline of §4)."""
    scores = jax.random.uniform(key, avail.shape)
    return _topk_mask(scores, avail, k)


def poc_select(key: jax.Array, avail: jnp.ndarray, m: jnp.ndarray,
               p: jnp.ndarray, losses: jnp.ndarray, d: int,
               topk: Optional[Callable] = None) -> jnp.ndarray:
    """Power-of-Choice: candidate set of size d sampled ∝ p_k from the
    available pool, then the top-m candidates by current loss are selected
    (Cho et al., the paper's loss-based baseline).  ``topk`` as in
    :func:`fedavg_select` — both cuts (candidate draw and loss cut) route
    through it."""
    cut = topk or _topk_mask
    cand = fedavg_select(key, avail, jnp.asarray(d, jnp.int32), p, topk=cut)
    return cut(losses, cand, m)


TOPK_IMPLS = ("stream", "allgather")


def _axis_size(axis: str) -> int:
    """Static size of a shard_map axis (psum of a concrete 1 constant-folds
    to the axis size at trace time)."""
    return int(jax.lax.psum(1, axis))


def _merge_desc(va, ga, vb, gb, keep: int):
    """Merge two (score, gid) candidate lists sorted by (−score, gid) and
    keep the best ``keep`` — the associative reduction step of the
    streaming top-k.  gids are globally unique, so (−score, gid) is a
    strict total order: merging pairwise and cutting to ``keep`` yields
    exactly the first ``keep`` entries of the fully-sorted union
    (top-k(A ∪ B) = top-k(top-k(A) ∪ top-k(B)))."""
    neg_v, g = jax.lax.sort((jnp.concatenate([-va, -vb]),
                             jnp.concatenate([ga, gb])), num_keys=2)
    return -neg_v[:keep], g[:keep]


def _stream_topk_candidates(vals, gids, axis: str, k_max: int):
    """Reduce per-shard sorted candidate lists to the replicated global
    top-``min(k_max, total)`` via ppermute rounds — no full ``all_gather``.

    Power-of-2 shard counts run a butterfly (log2(D) exchange+merge
    stages, partner ``i XOR 2^s``, list length capped at ``k_max``);
    other counts fall back to a ring reduction (D−1 single-neighbor
    steps).  Both are all-reduces: every shard ends with the same sorted
    global candidate list, in the exact (−score, gid) order the
    ``all_gather`` + global-sort path produces.
    """
    d = _axis_size(axis)
    kk = vals.shape[0]
    if d == 1:
        return vals, gids
    if d & (d - 1) == 0:                      # butterfly: log2(D) stages
        length = kk
        for s in range(d.bit_length() - 1):
            bit = 1 << s
            perm = [(j, j ^ bit) for j in range(d)]
            with collective_scope(axis):
                ov = jax.lax.ppermute(vals, axis, perm)
                og = jax.lax.ppermute(gids, axis, perm)
            length = min(int(k_max), 2 * length)
            vals, gids = _merge_desc(vals, gids, ov, og, length)
        return vals, gids
    # ring: pass a fixed-size buffer around, merging as it goes
    perm = [(j, (j + 1) % d) for j in range(d)]
    buf_v, buf_g = vals, gids
    for step in range(1, d):
        with collective_scope(axis):
            buf_v = jax.lax.ppermute(buf_v, axis, perm)
            buf_g = jax.lax.ppermute(buf_g, axis, perm)
        keep = min(int(k_max), kk * (step + 1))
        vals, gids = _merge_desc(vals, gids, buf_v, buf_g, keep)
    return vals, gids


def sharded_topk_mask(scores: jnp.ndarray, avail: jnp.ndarray,
                      k: jnp.ndarray, axis: str, k_max: int,
                      method: str = "allgather") -> jnp.ndarray:
    """Distributed :func:`_topk_mask` for use inside ``shard_map``.

    ``scores``/``avail`` are this shard's block of the client dimension.
    Per-shard top-``min(k_max, n_local)`` candidates are reduced to the
    global candidate list and cut at ``k_eff = min(k, |avail|)``, ordering
    by (−score, global id) — the exact tie-break of the single-device
    ``argsort`` path (stable sort ⇒ equal scores resolve to the lower
    client id; ``lax.top_k`` keeps the lower local index on ties,
    preserving that order within a shard).  Any globally-selected client
    is necessarily among its own shard's top-k_max, so the candidate cut
    loses nothing.  Returns this shard's (n_local,) boolean mask block,
    bit-identical to ``_topk_mask`` on the full arrays.

    ``method`` picks the reduction (``RunSpec.topk_impl``):

    * ``"allgather"`` — gather every shard's full candidate list and sort
      globally: O(D · min(k_max, N/D)) gathered pairs per shard, the
      reference spelling.
    * ``"stream"`` — merge candidate lists pairwise over ppermute rounds
      (:func:`_stream_topk_candidates`), so each shard moves O(k_max ·
      log D) pairs instead of the full candidate matrix, and membership
      is recovered by a scatter instead of an O(k_max · n_local)
      broadcast compare.  Same mask, bit for bit.
    """
    if method not in TOPK_IMPLS:
        raise ValueError(f"unknown sharded top-k method {method!r}; "
                         f"known: {TOPK_IMPLS}")
    with scope("topk"):
        return _sharded_topk_cut(scores, avail, k, axis, k_max, method)


def _sharded_topk_cut(scores, avail, k, axis: str, k_max: int, method: str):
    n_local = scores.shape[0]
    i = jax.lax.axis_index(axis)
    masked = jnp.where(avail, scores, _NEG)
    kk = min(int(k_max), n_local)
    vals, loc = jax.lax.top_k(masked, kk)
    gids = (loc + i * n_local).astype(jnp.int32)
    with collective_scope(axis):
        n_avail = jax.lax.psum(avail.sum().astype(jnp.int32), axis)
    k_eff = jnp.minimum(k.astype(jnp.int32), n_avail)
    if method == "stream":
        top_v, top_g = _stream_topk_candidates(vals, gids, axis, k_max)
        del top_v
        take = jnp.arange(top_g.shape[0], dtype=jnp.int32) < k_eff
        loc_ids = top_g - i * n_local
        in_shard = take & (loc_ids >= 0) & (loc_ids < n_local)
        hit = jnp.zeros((n_local,), bool).at[
            jnp.where(in_shard, loc_ids, 0)].max(in_shard)
        return hit & avail
    with collective_scope(axis):
        all_vals = jax.lax.all_gather(vals, axis, tiled=True)
        all_gids = jax.lax.all_gather(gids, axis, tiled=True)
    _, sorted_gids = jax.lax.sort((-all_vals, all_gids), num_keys=2)
    take = jnp.arange(sorted_gids.shape[0], dtype=jnp.int32) < k_eff
    sel_gids = jnp.where(take, sorted_gids, -1)
    local_gids = i * n_local + jnp.arange(n_local, dtype=jnp.int32)
    return (sel_gids[:, None] == local_gids[None, :]).any(axis=0) & avail


def cohort_ids_from_mask(mask: jnp.ndarray, cohort_size: int):
    """Selection mask (N,) bool → padded cohort (ids (K,) i32, valid (K,) bool).

    Jit-safe replacement for the host loop's ``np.flatnonzero`` + pad:
    selected ids in ascending order, slots past |S| repeating the first
    selected client with ``valid=False`` — the exact layout
    ``CohortSampler.cohort_batch`` produces, so the two paths stay
    batch-compatible (asserted by the engine parity tests).
    """
    n = mask.shape[0]
    ranked = jnp.sort(jnp.where(mask, jnp.arange(n, dtype=jnp.int32), n))
    ids = ranked[:cohort_size]
    valid = ids < n
    first = jnp.minimum(ranked[0], n - 1)   # mask is never empty in practice
    return jnp.where(valid, ids, first), valid


def _stream_min_ids(ids, axis: str, keep_max: int):
    """Replicated global lowest-``keep_max`` of per-shard ascending id
    lists via the same butterfly/ring schedule as the top-k reduction
    (ascending ids are just (−score, gid) candidates with equal scores)."""
    d = _axis_size(axis)
    kk = ids.shape[0]
    if d == 1:
        return ids

    def merge(a, b, keep):
        return jnp.sort(jnp.concatenate([a, b]))[:keep]

    if d & (d - 1) == 0:
        length = kk
        for s in range(d.bit_length() - 1):
            perm = [(j, j ^ (1 << s)) for j in range(d)]
            with collective_scope(axis):
                other = jax.lax.ppermute(ids, axis, perm)
            length = min(int(keep_max), 2 * length)
            ids = merge(ids, other, length)
        return ids
    perm = [(j, (j + 1) % d) for j in range(d)]
    buf = ids
    for step in range(1, d):
        with collective_scope(axis):
            buf = jax.lax.ppermute(buf, axis, perm)
        ids = merge(ids, buf, min(int(keep_max), kk * (step + 1)))
    return ids


def sharded_cohort_ids_from_mask(mask: jnp.ndarray, cohort_size: int,
                                 axis: str, n_total: int,
                                 method: str = "allgather"):
    """Distributed :func:`cohort_ids_from_mask` for use inside ``shard_map``.

    ``mask`` is this shard's block (which may cover padded clients — those
    are never set).  Each shard contributes its lowest-id selected clients
    (at most ``min(cohort_size, n_local)`` can be selected per shard since
    |S| ≤ cohort_size globally); the candidates are reduced to the global
    lowest ``cohort_size`` — via ``all_gather`` + sort, or with
    ``method="stream"`` via the ppermute merge schedule of
    :func:`sharded_topk_mask` (O(cohort · log D) ids moved instead of
    O(cohort · D)).  ``n_total`` is the *real* client count N — the same
    sentinel the single-device path uses — so the returned (ids, valid)
    are bit-identical to ``cohort_ids_from_mask`` on the full (N,) mask.
    The result is replicated across shards.
    """
    if method not in TOPK_IMPLS:
        raise ValueError(f"unknown sharded top-k method {method!r}; "
                         f"known: {TOPK_IMPLS}")
    n_local = mask.shape[0]
    i = jax.lax.axis_index(axis)
    gids = (i * n_local + jnp.arange(n_local, dtype=jnp.int32))
    ranked = jnp.sort(jnp.where(mask, gids, n_total))
    kk = min(int(cohort_size), n_local)
    if method == "stream":
        cand = _stream_min_ids(ranked[:kk], axis, cohort_size)
        cand = jnp.concatenate(          # streamed list may be < cohort_size
            [cand, jnp.full((max(0, cohort_size - cand.shape[0]),), n_total,
                            cand.dtype)])
    else:
        with collective_scope(axis):
            gathered = jax.lax.all_gather(ranked[:kk], axis, tiled=True)
        cand = jnp.sort(gathered)
    ids = cand[:cohort_size]
    valid = ids < n_total
    first = jnp.minimum(cand[0], n_total - 1)
    return jnp.where(valid, ids, first), valid
