"""Pallas TPU kernel for the F3AST per-round selection step (Alg. 1 l.4–5+9):

    mask  = top-min(K_t, |C_t|) available clients by score   (line 4)
    r(t)  = (1 − β) r(t−1) + β · 1_{S_t}                     (line 5)
    w_k   = weight rule on the cohort (p_k / r_k, 1/|S|, …)  (line 9)

This is the round's control plane — a chain of (N,)-vector ops XLA leaves
unfused (argsort + scatter + compare + EMA + renormalize reads the client
axis ~6×).  The kernel holds every (N,) operand as one lane-dense
``(rows, 128)`` VMEM block, so each streams HBM→VMEM exactly once, and
runs the cut with nothing but compares and full reductions — the two
things Mosaic lowers for any block size:

1. map each masked score to an order-preserving int32 key
   (:func:`_order_key`), so float order is integer order;
2. find the k_eff-th-largest key bit by bit, high bit first: a candidate
   prefix stays when at least k_eff keys are ``>=`` it (32 counting
   passes);
3. find the tie quota's id cutoff the same way: the largest id bound C
   with at most ``k_eff − |{key > thr}|`` ties below it (⌈log₂ n_pad⌉
   passes) — the stable ``(score, id)`` tie-break, lowest ids first;
4. one last pass writes the mask, the rate EMA and the elementwise part of
   the weight rule while the block is resident.

Each pass walks the block in ``_BLOCK_ROWS``-row slices inside a
``fori_loop``, so the program's size does not grow with N.

Bit-parity contract: the bisection finds the exact k_eff-th largest value
and the exact tie cutoff, so the mask is bit-identical to ``core.selection.
_topk_mask`` (see ``kernels.ref.topk_threshold_mask`` for the threshold
reformulation); the EMA and the elementwise weight arithmetic are op-for-op
the unfused ``update_rates`` / ``core.aggregation`` expressions.  The two
rules that normalise by a sum over the cohort (``uniform``, ``fedavg``)
take their sum outside the kernel, over the true-length ``(n,)`` vector,
so it associates exactly as the unfused path's does
(``tests/test_kernels_select.py``, ``tests/test_parity_matrix.py``).

Backend dispatch (``interpret=None``) differs deliberately from
``fed_aggregate``: on TPU the compiled kernel runs; elsewhere we dispatch
to the *fused jnp reference* (``kernels.ref.fed_select_ref``), NOT the
Pallas interpreter.  The interpreter is a debugging tool (~100× slow) and
selection is per-round hot-path.  ``interpret=True`` forces the
interpreter explicitly (the parity tests do).  :func:`dispatch_mode` names
the path a call takes; the engines report it as
``final_metrics["select_path"]``.

The compiled kernel holds all of its operands in VMEM (~36·N bytes for the
full select step), so N beyond ``MAX_KERNEL_N`` runs the fused reference;
docs/kernels.md records why the cap sits where it does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref
from .ref import SELECT_WEIGHT_MODES

# Largest client axis the single-block compiled kernel accepts: the largest
# power of two whose select-step operands fit the v5e's VMEM (128 MiB) at
# _VMEM_LIMIT (docs/kernels.md).  Beyond it the autodetect path runs the
# fused jnp reference, and dispatch_mode() says so.
MAX_KERNEL_N = 1 << 21

# Test/debug hook: when set, overrides the ``interpret=None`` autodetect.
# One of None | "compiled" | "interpret" | "ref".  The parity tests pin
# "interpret" to drive the engines through the actual Pallas kernel on CPU.
AUTODETECT_OVERRIDE = None

_LANES = 128
_BLOCK_ROWS = 256                  # rows per fori_loop step of every pass
_VMEM_LIMIT = 100 * 1024 * 1024    # scoped-VMEM cap raised for large N
_INT_MIN = np.int32(-2**31)


def dispatch_mode(n: int, interpret: bool | None = None) -> str:
    """The path a call over ``n`` clients takes: ``"compiled"`` (Mosaic
    kernel), ``"interpret"`` (Pallas interpreter) or ``"ref"`` (fused jnp
    reference).  Resolved per call (never at import), mirroring
    ``fed_aggregate._default_interpret`` so ``JAX_PLATFORMS`` is honored."""
    if interpret is True:
        return "interpret"
    if interpret is False:
        return "compiled"
    if AUTODETECT_OVERRIDE is not None:
        return AUTODETECT_OVERRIDE
    if jax.default_backend() == "tpu" and n <= MAX_KERNEL_N:
        return "compiled"
    return "ref"


def _layout(n: int) -> tuple[int, int]:
    """``(rows, block_rows)`` of the lane-dense block holding ``n`` values:
    rows a multiple of block_rows, block_rows a multiple of 8 (the f32/int32
    sublane tile)."""
    rows = -(-max(n, 1) // _LANES)
    rows = -(-rows // 8) * 8
    br = min(_BLOCK_ROWS, rows)
    return -(-rows // br) * br, br


def _to_block(x, rows: int):
    """(n,) → zero-padded (rows, 128)."""
    return jnp.pad(x, (0, rows * _LANES - x.shape[0])).reshape(rows, _LANES)


def _order_key(x):
    """f32 → int32 with ``x < y ⇔ key(x) < key(y)`` and ``key(x) == key(y)
    ⇔ x == y`` (−0.0 is folded onto +0.0 first, as float ``==`` does)."""
    x = jnp.where(x == 0.0, 0.0, x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(i < 0, i ^ np.int32(0x7FFFFFFF), i)


# ---------------------------------------------------------------------------
# Kernel bodies.  Refs are (rows, 128); k arrives in SMEM; key_ref is a VMEM
# scratch holding the order keys between passes.
# ---------------------------------------------------------------------------

def _cut(k_ref, scores_ref, avail_ref, key_ref, emit, *, rows: int,
         br: int):
    """Exact top-k_eff cut; calls ``emit(rows_slice, mask_block)`` once per
    row slice with the final (br, 128) bool mask."""
    nb = rows // br
    row = jax.lax.broadcasted_iota(jnp.int32, (br, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (br, _LANES), 1)
    zeros = jnp.zeros((br, _LANES), jnp.int32)

    def rows_of(i):
        return pl.ds(pl.multiple_of(i * br, br), br)

    def count(pred):
        """|{elements where pred(i, row_slice) holds}| as an int32 scalar."""
        def body(i, acc):
            return acc + pred(i, rows_of(i)).astype(jnp.int32)
        return jnp.sum(jax.lax.fori_loop(0, nb, body, zeros))

    def keys_pass(i, acc):
        s = rows_of(i)
        av = avail_ref[s, :] != 0
        key_ref[s, :] = _order_key(
            jnp.where(av, scores_ref[s, :], _ref.SELECT_NEG))
        return acc + av.astype(jnp.int32)

    n_avail = jnp.sum(jax.lax.fori_loop(0, nb, keys_pass, zeros))
    k_eff = jnp.minimum(k_ref[0], n_avail)

    # k_eff-th largest key, built high bit first in the unsigned image
    # (u = key ^ INT_MIN preserves order); k_eff == 0 ends at the maximum,
    # above which nothing is selected.
    def key_bit(b, t):
        cand = t | jnp.left_shift(jnp.int32(1), 31 - b)
        thr = cand ^ _INT_MIN
        c = count(lambda i, s: key_ref[s, :] >= thr)
        return jnp.where(c >= k_eff, cand, t)

    thr = jax.lax.fori_loop(0, 32, key_bit, jnp.int32(0)) ^ _INT_MIN
    quota = k_eff - count(lambda i, s: key_ref[s, :] > thr)

    def ids(i):
        return (i * br + row) * _LANES + lane

    def tie(s):
        return (key_ref[s, :] == thr) & (avail_ref[s, :] != 0)

    # largest id bound with at most `quota` ties below it: the first
    # `quota` ties in ascending id order (the stable tie-break)
    id_bits = (rows * _LANES).bit_length()

    def id_bit(b, c0):
        cand = c0 | jnp.left_shift(jnp.int32(1), id_bits - 1 - b)
        c = count(lambda i, s: tie(s) & (ids(i) < cand))
        return jnp.where(c <= quota, cand, c0)

    cut = jax.lax.fori_loop(0, id_bits, id_bit, jnp.int32(0))

    def emit_pass(i, carry):
        s = rows_of(i)
        mask = ((key_ref[s, :] > thr) | (tie(s) & (ids(i) < cut))) \
            & (avail_ref[s, :] != 0)
        emit(s, mask)
        return carry

    jax.lax.fori_loop(0, nb, emit_pass, 0)


def _mask_kernel(k_ref, scores_ref, avail_ref, mask_ref, key_ref, *,
                 rows: int, br: int):
    def emit(s, mask):
        mask_ref[s, :] = mask.astype(jnp.int32)

    _cut(k_ref, scores_ref, avail_ref, key_ref, emit, rows=rows, br=br)


def _select_kernel(k_ref, scores_ref, avail_ref, r_ref, p_ref, rw_ref,
                   mask_ref, newr_ref, w_ref, key_ref, *, beta: float,
                   weight_mode: str, rows: int, br: int):
    def emit(s, mask):
        # β is a *static* Python float so (1.0 − β) folds to the identical
        # f32 constant the unfused update_rates path uses — a traced SMEM β
        # would compute 1−β in f32 and could differ by 1 ulp.
        new_r = (1.0 - beta) * r_ref[s, :] + beta * mask.astype(jnp.float32)
        mask_ref[s, :] = mask.astype(jnp.int32)
        newr_ref[s, :] = new_r
        w_ref[s, :] = _ref.raw_select_weights(
            mask, new_r, p_ref[s, :], rw_ref[s, :], weight_mode)

    _cut(k_ref, scores_ref, avail_ref, key_ref, emit, rows=rows, br=br)


_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _call(kernel, n: int, n_in: int, n_out: int, *, interpret: bool):
    """pallas_call over whole (rows, 128) blocks: k in SMEM, ``n_in`` vector
    operands, ``n_out`` vector results (int32 mask first, then f32)."""
    rows, br = _layout(n)
    vec = pl.BlockSpec((rows, _LANES), lambda: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, _LANES), jnp.int32)]
    out_shape += [jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)] \
        * (n_out - 1)
    return pl.pallas_call(
        functools.partial(kernel, rows=rows, br=br),
        in_specs=[_SMEM_SPEC] + [vec] * n_in,
        out_specs=tuple([vec] * n_out),
        out_shape=tuple(out_shape),
        scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    ), rows


def _unblock(x, n: int):
    return x.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mask_pallas(scores, avail, k, *, interpret: bool):
    n = scores.shape[0]
    call, rows = _call(_mask_kernel, n, 2, 1, interpret=interpret)
    (mask,) = call(k.reshape(1), _to_block(scores, rows),
                   _to_block(avail.astype(jnp.int32), rows))
    return _unblock(mask, n) != 0


@functools.partial(jax.jit,
                   static_argnames=("beta", "weight_mode", "interpret"))
def _select_pallas(scores, avail, k, r, p, rw, *, beta: float,
                   weight_mode: str, interpret: bool):
    n = scores.shape[0]
    call, rows = _call(functools.partial(_select_kernel, beta=beta,
                                         weight_mode=weight_mode),
                       n, 5, 3, interpret=interpret)
    mask, new_r, w = call(
        k.reshape(1), _to_block(scores, rows),
        _to_block(avail.astype(jnp.int32), rows), _to_block(r, rows),
        _to_block(p, rows), _to_block(rw, rows))
    # the sum-normalised rules reduce over the true-length (n,) vector so
    # the denominator associates exactly as the unfused path's
    return (_unblock(mask, n) != 0, _unblock(new_r, n),
            _ref.normalize_select_weights(_unblock(w, n), weight_mode))


# jitted fused-jnp fallbacks (the off-TPU production path)
_mask_ref_jit = jax.jit(_ref.topk_threshold_mask)
_select_ref_jit = functools.partial(
    jax.jit, static_argnames=("weight_mode", "beta"))(
        lambda scores, avail, k, r, p, rw, *, beta, weight_mode:
        _ref.fed_select_ref(scores, avail, k, r, p, beta,
                            weight_mode=weight_mode, r_weight=rw))


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def fed_select_mask(scores: jnp.ndarray, avail: jnp.ndarray,
                    k: jnp.ndarray, *,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Fused top-k cut: drop-in for ``core.selection._topk_mask``.

    Same signature, bit-identical mask (stable ``(score, id)`` tie-break).
    Used by the strategy layer when a completion hook separates the
    selection cut from ``finalize`` — the EMA/weights then run on the
    *completed* mask and cannot be fused with the cut.

    ``interpret=None`` autodetects (:func:`dispatch_mode`): compiled Pallas
    on TPU, fused jnp reference elsewhere; ``interpret=True`` forces the
    Pallas interpreter.
    """
    k = jnp.asarray(k, jnp.int32)
    mode = dispatch_mode(scores.shape[0], interpret)
    if mode == "ref":
        return _mask_ref_jit(scores, avail, k)
    return _mask_pallas(scores, avail, k, interpret=(mode == "interpret"))


def fed_select(scores: jnp.ndarray, avail: jnp.ndarray, k: jnp.ndarray,
               r: jnp.ndarray, p: jnp.ndarray, beta: float, *,
               weight_mode: str = "unbiased", r_weight=None,
               interpret: bool | None = None):
    """The fused selection step: ``(mask, new_r, weights)`` in one pass.

    ``scores``/``avail``/``r``/``p``: (N,) round inputs; ``k``: the round
    budget K_t (traced int scalar); ``beta``: the rate-EMA step (static
    Python float).  ``weight_mode`` picks the built-in weight rule (see
    ``kernels.ref.select_weights_ref``); ``unbiased_frozen`` additionally
    needs ``r_weight`` — the frozen (N,) rate Alg. 2 weights against.

    Bit-identical to the unfused pipeline ``_topk_mask`` → ``update_rates``
    → weight rule, on every backend mode (asserted in
    ``tests/test_kernels_select.py``).  ``interpret=None`` autodetects as
    in :func:`fed_select_mask`.
    """
    if weight_mode not in SELECT_WEIGHT_MODES:
        raise ValueError(f"unknown weight_mode {weight_mode!r}; "
                         f"known: {SELECT_WEIGHT_MODES}")
    if weight_mode == "unbiased_frozen" and r_weight is None:
        raise ValueError("weight_mode='unbiased_frozen' needs r_weight= "
                         "(the frozen target rate)")
    beta = float(beta)
    k = jnp.asarray(k, jnp.int32)
    rw = p if r_weight is None else jnp.asarray(r_weight, jnp.float32)
    mode = dispatch_mode(scores.shape[0], interpret)
    if mode == "ref":
        return _select_ref_jit(scores, avail, k, r, p, rw, beta=beta,
                               weight_mode=weight_mode)
    return _select_pallas(scores, avail, k, r, p, rw, beta=beta,
                          weight_mode=weight_mode,
                          interpret=(mode == "interpret"))
