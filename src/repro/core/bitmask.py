"""Bit-packed boolean client masks: (N,) bool ⇄ (ceil(N/32),) uint32.

At N = 1e6–1e7 the per-round (N,) bool traffic — selection/completion
masks streamed out of the compiled round loop, and the full-width mask
``all_gather``s inside the sharded engine — becomes the dominant data
movement of a round (the model is tiny; the cohort batch is (K, E, B)).
Packing 32 clients per ``uint32`` word cuts that traffic 8× (jax bools
are byte-sized) without touching the semantics: engines pack at the
producer, drivers unpack once per chunk on the host.  The host decode
(``unpack_bits_np``) is a byte-wise ``np.unpackbits`` over the words'
little-endian bytes that writes the (…, n) bool mask once, with no
per-bit temporaries.

Layout (little-endian within a word): bit ``j`` of word ``w`` is client
``32*w + j``, so ``unpack(pack(m))[:n] == m`` and concatenating packed
per-shard blocks of a client dimension whose per-shard length is a
multiple of 32 equals packing the concatenated mask — the property the
sharded engine's per-shard streaming relies on (``tests/
test_engine_sharded.py`` pins both).

Pad bits (clients ``>= n`` in the last word) pack as 0 and unpack as
False; ``pack_bits`` of an already-padded mask is exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .spans import collective_scope

__all__ = ["all_gather_bits", "n_words", "pack_bits", "unpack_bits",
           "unpack_bits_np"]

_WORD = 32


def n_words(n: int) -> int:
    """Packed word count for an ``n``-bit mask: ceil(n / 32)."""
    return -(-int(n) // _WORD)


def pack_bits(mask: jnp.ndarray) -> jnp.ndarray:
    """(…, N) bool → (…, ceil(N/32)) uint32 (little-endian bit order)."""
    n = mask.shape[-1]
    w = n_words(n)
    pad = w * _WORD - n
    bits = mask.astype(jnp.uint32)
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (mask.ndim - 1) + [(0, pad)])
    bits = bits.reshape(mask.shape[:-1] + (w, _WORD))
    # explicit broadcast of the shift vector: bit-identical, and clean
    # under jax_numpy_rank_promotion="raise" (REPRO_SANITIZE=1)
    shifts = jnp.broadcast_to(jnp.arange(_WORD, dtype=jnp.uint32), bits.shape)
    return (bits << shifts).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jnp.ndarray, n: int) -> jnp.ndarray:
    """(…, W) uint32 → (…, n) bool with ``n <= 32*W`` (inverse of pack)."""
    expanded = words[..., :, None]
    shifts = jnp.broadcast_to(jnp.arange(_WORD, dtype=jnp.uint32),
                              expanded.shape[:-1] + (_WORD,))
    bits = (expanded >> shifts) & 1
    flat = bits.reshape(words.shape[:-1] + (words.shape[-1] * _WORD,))
    return flat[..., :n].astype(bool)


def unpack_bits_np(words: np.ndarray, n: int) -> np.ndarray:
    """Host-side :func:`unpack_bits` for driver-side chunk streams.

    (…, W) words → (…, n) bool, ``n <= 32*W`` (else ``ValueError``).
    The words are taken as C-contiguous little-endian uint32 (other
    integer dtypes are cast), read as bytes, and decoded by
    ``np.unpackbits``' byte-wise table straight into the (…, n) output:
    ``count`` drops the pad bits past ``n`` and the 0/1 bytes are viewed
    as bool, so the mask is written once.
    """
    words = np.ascontiguousarray(words, dtype="<u4")
    if not 0 <= n <= _WORD * words.shape[-1]:
        raise ValueError(f"n={n} bits do not fit in {words.shape[-1]} "
                         f"words of {_WORD}")
    bits = np.unpackbits(words.view(np.uint8), axis=-1, count=n,
                         bitorder="little")
    return bits.view(bool)


def all_gather_bits(mask_blk: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """Packed ``all_gather`` of a per-shard (n_local,) bool block → (n,) bool.

    Drop-in for ``lax.all_gather(mask_blk, axis, tiled=True)[:n]`` inside
    ``shard_map``: when the shard block length is a multiple of 32 the
    gather moves uint32 words (8× less traffic) and unpacks locally;
    otherwise per-shard pad bits would interleave mid-mask, so it falls
    back to the plain bool gather — identical result either way.
    """
    n_local = mask_blk.shape[0]
    if n_local % _WORD:
        with collective_scope(axis):
            return jax.lax.all_gather(mask_blk, axis, tiled=True)[:n]
    packed = pack_bits(mask_blk)
    with collective_scope(axis):
        words = jax.lax.all_gather(packed, axis, tiled=True)
    return unpack_bits(words, words.shape[0] * _WORD)[:n]
