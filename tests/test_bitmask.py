"""Bit-packed mask codec (``repro.core.bitmask``).

The engines stream selection/completion masks as uint32 words and the
sharded engine gathers them packed across shards; everything downstream
assumes ``unpack(pack(m)) == m`` exactly, that pad bits never leak, and
that concatenating per-shard packed blocks (shard length % 32 == 0)
equals packing the concatenated mask.  These tests pin each property.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.bitmask import (all_gather_bits, n_words, pack_bits,
                                unpack_bits, unpack_bits_np)
from repro.launch.mesh import make_client_mesh


@pytest.mark.parametrize("n", [1, 31, 32, 33, 64, 100, 257])
def test_pack_unpack_round_trip(n):
    rng = np.random.default_rng(n)
    mask = rng.random(n) < 0.5
    words = pack_bits(jnp.asarray(mask))
    assert words.shape == (n_words(n),)
    assert words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(unpack_bits(words, n)), mask)
    np.testing.assert_array_equal(unpack_bits_np(np.asarray(words), n), mask)


def test_pack_unpack_leading_batch_dims():
    rng = np.random.default_rng(0)
    mask = rng.random((4, 5, 100)) < 0.3
    words = pack_bits(jnp.asarray(mask))
    assert words.shape == (4, 5, n_words(100))
    np.testing.assert_array_equal(np.asarray(unpack_bits(words, 100)), mask)
    np.testing.assert_array_equal(unpack_bits_np(np.asarray(words), 100),
                                  mask)


def test_pad_bits_pack_to_zero_and_unpack_false():
    # clients >= n occupy the tail of the last word: they must read as 0
    # so a packed padded mask is indistinguishable from the padded mask
    mask = np.ones(33, bool)
    words = np.asarray(pack_bits(jnp.asarray(mask)))
    assert words[1] == 1                      # only bit 0 of word 1 set
    assert not np.asarray(unpack_bits(jnp.asarray(words), 40))[33:].any()


def test_little_endian_bit_layout():
    # bit j of word w is client 32*w + j — the layout DESIGN.md documents
    mask = np.zeros(64, bool)
    mask[[0, 5, 32]] = True
    words = np.asarray(pack_bits(jnp.asarray(mask)))
    np.testing.assert_array_equal(words, [(1 << 0) | (1 << 5), 1])


def test_per_shard_concat_equals_full_pack():
    # shard blocks of length % 32 == 0: concatenating the per-shard packed
    # words equals packing the full mask — the invariant the sharded
    # engine's streamed (C, n_pad/32) output relies on
    rng = np.random.default_rng(3)
    mask = rng.random(8 * 64) < 0.4
    full = np.asarray(pack_bits(jnp.asarray(mask)))
    per_shard = np.concatenate(
        [np.asarray(pack_bits(jnp.asarray(mask[lo:lo + 64])))
         for lo in range(0, mask.size, 64)])
    np.testing.assert_array_equal(per_shard, full)


def _unpack_reference(words, n):
    # the broadcast-shift decode: one (…, W, 32) word per bit, then slice
    words = np.asarray(words, np.uint32)
    bits = (words[..., :, None] >> np.arange(32, dtype=np.uint32)) & 1
    flat = bits.reshape(words.shape[:-1] + (words.shape[-1] * 32,))
    return flat[..., :n].astype(bool)


def _random_words(rng, lead, n):
    return rng.integers(0, 1 << 32, size=lead + (n_words(n),),
                        dtype=np.uint64).astype(np.uint32)


@pytest.mark.parametrize("lead", [(), (10,), (8, 10)])
@pytest.mark.parametrize("n", [1, 31, 32, 33, 100, 257, 100_003])
def test_unpack_bits_np_matches_reference(n, lead):
    # random words, pad bits included: the byte-wise decode equals the
    # bit-by-bit one on every leading shape the drivers stream
    words = _random_words(np.random.default_rng(n), lead, n)
    got = unpack_bits_np(words, n)
    assert got.shape == lead + (n,)
    np.testing.assert_array_equal(got, _unpack_reference(words, n))


@pytest.mark.parametrize("make", [
    lambda w: w.view(np.int32),                  # signed words
    lambda w: w.astype(">u4"),                   # big-endian words
    lambda w: np.repeat(w, 2, axis=-1)[..., ::2],  # non-contiguous slice
], ids=["int32", "big_endian", "strided"])
def test_unpack_bits_np_accepts_driver_inputs(make):
    n = 1000
    words = _random_words(np.random.default_rng(7), (10,), n)
    np.testing.assert_array_equal(unpack_bits_np(make(words), n),
                                  _unpack_reference(words, n))


def test_unpack_bits_np_output_contract():
    # every bit set, so the last word's 32*W - n pad bits are 1 on input
    n = 70
    words = np.full((3, n_words(n)), 0xFFFFFFFF, np.uint32)
    got = unpack_bits_np(words, n)
    assert got.dtype == np.bool_
    assert got.shape == (3, n)
    assert got.flags.c_contiguous and got.flags.writeable
    assert got.all()                             # n real bits, no pad
    got[0, 0] = False                            # a mask of its own
    assert words[0, 0] == 0xFFFFFFFF


def test_unpack_bits_np_rejects_n_past_the_words():
    words = np.zeros((2, 3), np.uint32)
    assert unpack_bits_np(words, 96).shape == (2, 96)
    with pytest.raises(ValueError):
        unpack_bits_np(words, 97)


@pytest.mark.parametrize("n_local", [32, 24])   # packed path / bool fallback
def test_all_gather_bits_matches_bool_gather(n_local):
    mesh = make_client_mesh(axis_name="clients")
    shards = mesh.shape["clients"]
    n = n_local * shards - 3                    # real N below the pad
    rng = np.random.default_rng(n_local)
    mask = np.zeros(n_local * shards, bool)
    mask[:n] = rng.random(n) < 0.5

    f = jax.jit(jax.shard_map(
        lambda m: all_gather_bits(m, "clients", n),
        mesh=mesh, in_specs=P("clients"), out_specs=P(),
        check_vma=False))
    got = np.asarray(f(jnp.asarray(mask)))
    assert got.shape == (n,)
    np.testing.assert_array_equal(got, mask[:n])
