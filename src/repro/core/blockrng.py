"""Slice-consistent PRNG draws — per-shard blocks of a full-width stream.

The parity contract draws every random field (availability, selection
tie-breaks, minibatch indices) at the full (N,) client shape from a
replicated key, so all engines see bit-identical values; the sharded
engine then slices its own block.  Materializing the (N,) draw on every
shard makes the replicated RNG the dominant cost of a million-client
round: three full-width draws per round × D shards is ~D× the work the
unsharded engine does.

JAX's default ``threefry2x32`` generator is counter-based: element ``i``
of ``random_bits(key, 32, (n,))`` is a pure function of ``key`` and the
lane pair ``(i mod m, m + i mod m)`` with ``m = ceil(n/2)`` (the counter
vector is split in half and hashed pairwise, the two output halves are
concatenated).  A shard can therefore compute *exactly* the slice
``[off, off + n_local)`` of the full-width draw from its own lane
indices, at O(n_local) cost — bitwise-identical to slicing, with no
(N,)-shaped intermediate anywhere (``tests/test_blockrng.py`` pins this
against ``jax.random`` for even/odd n and blocks straddling the counter
midpoint).

Only the default threefry implementation has this layout.  For any other
key — a different PRNG impl, typed keys of another flavor, or
``jax_threefry_partitionable`` enabled (which changes the counter
layout) — every helper falls back to the full-width draw + slice:
always correct, just not O(n_local).

Out-of-range lanes (``off + j >= n_total``, the shard-padding tail) are
clamped to lane 0: their values are well-defined garbage and callers mask
them (the engines' padded clients are never available, never selected,
and score 0).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["block_bits", "block_bernoulli", "block_uniform",
           "have_block_prng"]

from jax._src.prng import threefry_2x32 as _threefry_2x32


def _raw_threefry_key(key):
    """The (2,) uint32 key data iff ``key`` is a threefry key, else None."""
    key = jnp.asarray(key)
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        impl = jax.random.key_impl(key)
        if "threefry" not in str(impl):
            return None
        key = jax.random.key_data(key)
    if key.dtype != jnp.uint32 or key.shape != (2,):
        return None
    return key


def have_block_prng(key) -> bool:
    """True when O(n_local) block draws are available for ``key``."""
    return (not jax.config.jax_threefry_partitionable
            and _raw_threefry_key(key) is not None)


def block_bits(key, n_total: int, off, n_local: int) -> jnp.ndarray:
    """``random_bits(key, 32, (n_total,))[off:off + n_local]``, bitwise.

    ``off`` may be traced (the sharded engine passes ``axis_index * nl``);
    ``n_total`` and ``n_local`` are static.
    """
    if not have_block_prng(key):
        full = jax.random.bits(key, (n_total,), jnp.uint32)
        return _fallback_slice(full, off, n_local)
    key = _raw_threefry_key(key)
    m = (n_total + 1) // 2               # counter midpoint (odd n pads one
    i = (jnp.asarray(off, jnp.uint32)    # zero lane)
         + jnp.arange(n_local, dtype=jnp.uint32))
    i = jnp.where(i < n_total, i, 0)     # shard-padding tail: clamp
    in_first = i < m
    lane = jnp.where(in_first, i, i - m)
    partner = lane + m
    x1 = jnp.where(partner < n_total, partner, 0).astype(jnp.uint32)
    out = _threefry_2x32(key, jnp.concatenate([lane, x1]))
    return jnp.where(in_first, out[:n_local], out[n_local:])


def block_uniform(key, n_total: int, off, n_local: int) -> jnp.ndarray:
    """``jax.random.uniform(key, (n_total,))[off:off + n_local]``, bitwise.

    Same mantissa-fill construction as ``jax.random.uniform`` for float32
    [0, 1): top 23 random bits into the mantissa of 1.0 ≤ x < 2.0, minus 1.
    """
    if not have_block_prng(key):
        full = jax.random.uniform(key, (n_total,))
        return _fallback_slice(full, off, n_local)
    bits = block_bits(key, n_total, off, n_local)
    fbits = (bits >> np.uint32(9)) | np.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(fbits, jnp.float32) - 1.0


def block_bernoulli(key, p_block, n_total: int, off,
                    n_local: int) -> jnp.ndarray:
    """``jax.random.bernoulli(key, p_full)[off:off + n_local]``, bitwise,
    given this block's slice of the probabilities (scalar or (n_local,))."""
    return block_uniform(key, n_total, off, n_local) < p_block


def _fallback_slice(full, off, n_local):
    # dynamic_slice clamps the start index, which would alias the tail of
    # the real stream onto out-of-range lanes; pad first so those lanes
    # read zeros instead (callers mask them either way)
    return jax.lax.dynamic_slice_in_dim(
        jnp.pad(full, (0, n_local)), off, n_local)
