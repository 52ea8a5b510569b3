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
