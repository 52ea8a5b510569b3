"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0):
    """Dense-softmax GQA attention — mirrors models.layers._dense_sdpa."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    g = H // KV
    qg = q.reshape(B, Sq, KV, g, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(hd).astype(jnp.float32)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def fed_aggregate_ref(deltas, weights):
    """(K, D), (K,) -> (D,): f32-accumulated weighted sum."""
    acc = jnp.sum(deltas.astype(jnp.float32) * weights[:, None].astype(jnp.float32),
                  axis=0)
    return acc.astype(deltas.dtype)


# ---------------------------------------------------------------------------
# fed_select: fused selection pipeline (kernels/fed_select.py)
# ---------------------------------------------------------------------------

# Sentinel for unavailable clients — must match core.selection._NEG so the
# threshold cut reproduces ``_topk_mask`` bit-for-bit.
SELECT_NEG = -1e30

SELECT_WEIGHT_MODES = ("unbiased", "unbiased_frozen", "uniform", "fedavg")


def topk_threshold_mask(scores, avail, k):
    """``core.selection._topk_mask`` reformulated as a threshold cut.

    ``_topk_mask`` ranks via a stable ``argsort(-masked)`` and keeps ranks
    ``< k_eff``; equivalently, with ``thr`` the ``k_eff``-th largest masked
    score, the selected set is

        {i : masked_i > thr}  ∪  the first (k_eff − |{masked > thr}|)
                                 ties (masked_i == thr) in ascending id order

    which needs only a *value* sort (no argsort + scatter) plus a cumsum —
    cheaper and fusable.  The tie prefix in ascending id order is exactly
    the stable-sort ``(score, id)`` tie-break, so the returned mask is
    bit-identical to ``_topk_mask`` (asserted in
    ``tests/test_kernels_select.py``).  The Pallas kernel finds the same
    ``thr`` and tie prefix by bisection instead of a sort and a cumsum
    (``kernels/fed_select.py``).
    """
    n = scores.shape[0]
    avail = avail.astype(bool)
    masked = jnp.where(avail, scores, SELECT_NEG).astype(jnp.float32)
    n_avail = jnp.sum(avail.astype(jnp.int32))
    k_eff = jnp.minimum(k.astype(jnp.int32), n_avail)
    # k_eff-th largest lives at ascending index n - k_eff; k_eff == 0 clips
    # to the maximum, for which the gt/tie counts below select nothing.
    thr = jnp.sort(masked)[jnp.clip(n - k_eff, 0, n - 1)]
    gt = masked > thr
    g = jnp.sum(gt.astype(jnp.int32))
    eq = (masked == thr) & avail
    eq_i = eq.astype(jnp.int32)
    tie_rank = jnp.cumsum(eq_i) - eq_i           # exclusive: id-order prefix
    return (gt | (eq & (tie_rank < (k_eff - g)))) & avail


def raw_select_weights(mask, new_r, p, r_weight, weight_mode: str):
    """The elementwise half of :func:`select_weights_ref` (the kernel
    computes it in its last pass; :func:`normalize_select_weights` adds the
    cohort-sum half)."""
    from ..core.hfun import R_MIN
    if weight_mode == "unbiased":
        return jnp.where(mask, p / jnp.maximum(new_r, R_MIN), 0.0)
    if weight_mode == "unbiased_frozen":
        return jnp.where(mask, p / jnp.maximum(r_weight, R_MIN), 0.0)
    if weight_mode == "uniform":
        return mask.astype(jnp.float32)
    if weight_mode == "fedavg":
        return jnp.where(mask, p, 0.0)
    raise ValueError(f"unknown weight_mode {weight_mode!r}; "
                     f"known: {SELECT_WEIGHT_MODES}")


def normalize_select_weights(w, weight_mode: str):
    """Divide the sum-normalised rules by their cohort sum over the
    true-length (N,) vector — the same association as ``core.aggregation``."""
    if weight_mode == "uniform":
        return w / jnp.maximum(w.sum(), 1.0)
    if weight_mode == "fedavg":
        return w / jnp.maximum(w.sum(), 1e-12)
    return w


def select_weights_ref(mask, new_r, p, r_weight, weight_mode: str):
    """The built-in strategies' weight rules on the fused mask.

    Mirrors ``core.aggregation`` exactly (op-for-op, so the fused path is
    bit-identical to the unfused ``finalize``):

    * ``unbiased``        p_k / max(r_k(t), R_MIN) on the cohort (Alg. 1
                          line 9, f3ast — uses the *updated* EMA)
    * ``unbiased_frozen`` p_k / max(r_weight_k, R_MIN) (Alg. 2,
                          fixed_f3ast — frozen target / pre-update rate)
    * ``uniform``         1/|S| over the cohort (fedavg, uniform)
    * ``fedavg``          p_k / Σ_{S} p_k  (fedavg_weighted)
    """
    return normalize_select_weights(
        raw_select_weights(mask, new_r, p, r_weight, weight_mode),
        weight_mode)


def fed_select_ref(scores, avail, k, r, p, beta, *,
                   weight_mode: str = "unbiased", r_weight=None):
    """jnp oracle for the fused selection step: (mask, new_r, weights).

    One pass of Alg. 1 lines 4–5 + the line-9 weight rule: threshold top-k
    cut → r_k EMA ``r(t) = (1−β) r(t−1) + β·1_{S_t}`` → cohort weights.
    The Pallas kernel's allclose-and-bitwise target.
    """
    mask = topk_threshold_mask(scores, avail, k)
    new_r = (1.0 - beta) * r + beta * mask.astype(jnp.float32)
    w = select_weights_ref(mask, new_r, p, r_weight, weight_mode)
    return mask, new_r, w


def ssd_chunk_ref(x, dt, A, Bm, Cm):
    """Intra-chunk SSD pieces — mirrors models.ssm._ssd_chunked internals.

    Returns (y_intra, states, decays) with the same shapes as the kernel.
    """
    a = dt * A[None, None, None, :]                       # (B, nc, Q, H)
    cum = jnp.cumsum(a, axis=2)
    Q = x.shape[2]
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(tri[None, None, :, :, None], jnp.exp(diff), 0.0)
    scores = jnp.einsum("bcin,bcjn->bcij", Cm.astype(jnp.float32),
                        Bm.astype(jnp.float32))
    M = scores[..., None] * L
    xdt = x.astype(jnp.float32) * dt[..., None]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M, xdt)
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)
    states = jnp.einsum("bcjh,bcjn,bcjhp->bchnp", decay_to_end * dt,
                        Bm.astype(jnp.float32), x.astype(jnp.float32))
    decays = jnp.exp(cum[:, :, -1, :])
    return y_intra, states, decays


def ssd_ref(x, dt, A, Bm, Cm, chunk: int):
    """Full SSD (intra + inter) — delegates to the model's reference path."""
    from ..models.ssm import _ssd_chunked
    return _ssd_chunked(x, dt, A, Bm, Cm, chunk)
