"""Pallas TPU kernel for the F3AST aggregation step (paper Alg. 1 line 9):

    Delta[d] = sum_k  w_k * v[k, d]        w_k = p_k / r_k (masked)

This is the server-side reduction of cohort deltas — a bandwidth-bound
weighted masked sum over the cohort axis.  Tiling: the parameter dimension
is viewed lane-dense as (D/128, 128) and split into (tile/128, 128) VMEM
tiles (grid axis 0, the cohort dimension squeezed out of each block); the
cohort axis K is the innermost grid axis, accumulated in an f32 VMEM
scratch so each delta tile streams HBM->VMEM exactly once (arithmetic
intensity ~= 1 FLOP/byte — pure HBM-bandwidth roofline, which is why a
fused kernel rather than K separate scaled adds is worth it: XLA's unfused
form reads the accumulator K times).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE = 8 * 1024
_LANES = 128


def _agg_kernel(w_ref, v_ref, o_ref, acc_ref, *, nk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_k = w_ref[ki]
    acc_ref[...] += w_k * v_ref[...].astype(jnp.float32)

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _default_interpret() -> bool:
    """Interpret off TPU, compiled Pallas on TPU.

    Resolved per call (not at import) so backend selection via
    ``JAX_PLATFORMS`` / ``jax.config`` is honored; on TPU the kernel must
    never silently run under the interpreter — that is a ~100× slowdown on
    the round's hot reduction.
    """
    return jax.default_backend() != "tpu"


def fed_aggregate(deltas: jnp.ndarray, weights: jnp.ndarray, *,
                  tile: int = DEFAULT_TILE,
                  interpret: bool | None = None):
    """Algorithm 1 line 9 as a fused reduction: Δ^{t+1} = Σ_k w_k v_k.

    With w_k = p_k / r_k(t) this is the unbiased F3AST estimator (Lemma
    C.1: E[Δ] equals the full-participation update); padded cohort slots
    carry w_k = 0.  ``deltas``: (K, D) flattened cohort deltas; ``weights``:
    (K,) f32.  Returns (D,) in ``deltas.dtype`` with f32 accumulation
    inside the kernel.  Matches the jnp reference ``kernels.ref.
    fed_aggregate_ref`` (asserted in ``tests/test_kernels.py``) and computes
    the same sum as ``core.aggregation.weighted_aggregate`` — this is the
    TPU-roofline spelling.

    ``interpret=None`` (default) auto-detects: compiled Pallas on TPU,
    interpreter elsewhere.
    """
    if interpret is None:
        interpret = _default_interpret()
    return _fed_aggregate(deltas, weights, tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _fed_aggregate(deltas: jnp.ndarray, weights: jnp.ndarray, *,
                   tile: int, interpret: bool):
    K, D = deltas.shape
    if tile % (8 * _LANES):
        raise ValueError(f"tile must be a multiple of {8 * _LANES}, "
                         f"got {tile}")
    pad = (-D) % tile
    if pad:
        deltas = jnp.pad(deltas, ((0, 0), (0, pad)))
    Dp = D + pad
    nd = Dp // tile
    tr = tile // _LANES                  # rows of one (tr, 128) tile

    out = pl.pallas_call(
        functools.partial(_agg_kernel, nk=K),
        grid=(nd, K),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, tr, _LANES), lambda d, k: (k, d, 0)),
        ],
        out_specs=pl.BlockSpec((tr, _LANES), lambda d, k: (d, 0)),
        out_shape=jax.ShapeDtypeStruct((Dp // _LANES, _LANES), deltas.dtype),
        scratch_shapes=[pltpu.VMEM((tr, _LANES), jnp.float32)],
        interpret=interpret,
    )(weights.astype(jnp.float32), deltas.reshape(K, Dp // _LANES, _LANES))
    return out.reshape(Dp)[:D]


def fed_aggregate_tree(deltas_tree, weights: jnp.ndarray, *,
                       interpret: bool | None = None):
    """Pytree spelling of Alg. 1 line 9: flattens each (K, ...) model leaf
    to (K, D), applies :func:`fed_aggregate` with the same (K,) weight
    vector (one w_k per cohort client spans every parameter leaf), and
    restores the leaf shapes — the whole-model Δ^{t+1} in one call.
    ``interpret=None`` auto-detects the backend like :func:`fed_aggregate`."""
    def one(leaf):
        K = leaf.shape[0]
        flat = leaf.reshape(K, -1)
        return fed_aggregate(flat, weights, interpret=interpret
                             ).reshape(leaf.shape[1:])
    return jax.tree.map(one, deltas_tree)
