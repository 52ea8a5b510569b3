"""Slice-consistent PRNG blocks (``repro.core.blockrng``).

The sharded engine's parity contract draws every random field at full
(N,) shape from a replicated key; the blockwise fast paths instead
compute each shard's slice directly from threefry counters.  These tests
pin the load-bearing property — ``block_*(key, n, off, nl)`` is
*bitwise* equal to slicing the full-width ``jax.random`` draw — for even
and odd n, blocks straddling the counter midpoint, out-of-range tails,
and the full-draw fallback, plus the blockwise Bernoulli availability
step (including the forced-non-empty collective) against the full-width
step it must shadow.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import blockrng
from repro.core.availability import force_nonempty, force_nonempty_block
from repro.core.blockrng import block_bernoulli, block_bits, block_uniform
from repro.launch.mesh import make_client_mesh
from repro.sim.processes import make_process


@pytest.mark.parametrize("n", [7, 64, 101, 1000, 1001])
def test_block_bits_and_uniform_match_slices(n):
    key = jax.random.PRNGKey(n)
    bits_full = jax.random.bits(key, (n,), jnp.uint32)
    unif_full = jax.random.uniform(key, (n,))
    m = (n + 1) // 2
    # blocks at the head, straddling the counter midpoint, and at the tail
    windows = [(0, min(8, n)), (max(0, m - 3), min(7, n - max(0, m - 3))),
               (max(0, n - 5), min(5, n))]
    for off, nl in windows:
        np.testing.assert_array_equal(
            np.asarray(block_bits(key, n, off, nl)),
            np.asarray(bits_full[off:off + nl]))
        np.testing.assert_array_equal(
            np.asarray(block_uniform(key, n, off, nl)),
            np.asarray(unif_full[off:off + nl]))


def test_block_bernoulli_matches_slice_heterogeneous():
    n = 500
    key = jax.random.PRNGKey(3)
    q = jnp.linspace(0.05, 0.9, n)
    full = jax.random.bernoulli(key, q)
    off, nl = 123, 77
    blk = block_bernoulli(key, q[off:off + nl], n, off, nl)
    np.testing.assert_array_equal(np.asarray(blk),
                                  np.asarray(full[off:off + nl]))


def test_block_tail_lanes_defined_and_in_range_exact():
    # off + nl past n: in-range lanes stay bitwise exact, tail lanes are
    # well-defined (clamped) — callers mask them
    n, off, nl = 100, 96, 16
    key = jax.random.PRNGKey(0)
    full = jax.random.uniform(key, (n,))
    blk = block_uniform(key, n, off, nl)
    np.testing.assert_array_equal(np.asarray(blk[:4]), np.asarray(full[96:]))
    assert np.isfinite(np.asarray(blk)).all()


def test_fallback_path_matches(monkeypatch):
    # no block PRNG for the key -> full draw + slice; same in-range values
    # as the O(n_local) path (which needs the non-partitionable layout)
    key = jax.random.PRNGKey(9)
    with jax.threefry_partitionable(False):
        assert blockrng.have_block_prng(key)
        want = np.asarray(block_uniform(key, 200, 50, 60))
        monkeypatch.setattr(blockrng, "have_block_prng", lambda key: False)
        got = np.asarray(block_uniform(key, 200, 50, 60))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("all_down", [False, True])
def test_force_nonempty_block_matches_full(all_down):
    mesh = make_client_mesh(axis_name="clients")
    shards = mesh.shape["clients"]
    n = 64 * shards
    key = jax.random.PRNGKey(5)
    q = jnp.linspace(0.1, 0.8, n)
    mask = (jnp.zeros(n, bool) if all_down
            else jax.random.bernoulli(key, q))
    tie_key = jax.random.fold_in(key, 1)
    want = force_nonempty(mask, q, tie_key)

    def blk_fn(mask_blk, q_blk):
        nl = mask_blk.shape[0]
        off = jax.lax.axis_index("clients") * nl
        tie = block_uniform(tie_key, n, off, nl)
        cand = jnp.where(q_blk >= q.max(), tie, -1.0)
        return force_nonempty_block(mask_blk, cand, off, "clients")

    got = jax.jit(jax.shard_map(
        blk_fn, mesh=mesh, in_specs=(P("clients"), P("clients")),
        out_specs=P("clients"), check_vma=False))(mask, q)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("sigma", [0.0, 1.0])
def test_bernoulli_step_block_matches_step(sigma):
    mesh = make_client_mesh(axis_name="clients")
    shards = mesh.shape["clients"]
    n = 96 * shards - 17                       # real N below the pad
    n_pad = 96 * shards
    model = make_process("bernoulli", n, q=0.3, sigma=sigma)
    assert hasattr(model, "step_block")
    key = jax.random.PRNGKey(11)
    _, full = model.step(key, (), 0)

    def blk_fn():
        nl = n_pad // shards
        off = jax.lax.axis_index("clients") * nl
        _, mask_blk = model.step_block(key, (), 0, off=off, n_local=nl,
                                       axis="clients")
        return mask_blk

    got = np.asarray(jax.jit(jax.shard_map(
        blk_fn, mesh=mesh, in_specs=(), out_specs=P("clients"),
        check_vma=False))())
    np.testing.assert_array_equal(got[:n], np.asarray(full))
    assert not got[n:].any()                   # pad lanes never available


def test_bernoulli_step_block_forces_nonempty():
    # q = 0 draws an all-down round: exactly one client must wake, the
    # same one the full-width step wakes
    mesh = make_client_mesh(axis_name="clients")
    shards = mesh.shape["clients"]
    n = 32 * shards
    model = make_process("bernoulli", n, q=0.0)
    key = jax.random.PRNGKey(2)
    _, full = model.step(key, (), 0)
    assert np.asarray(full).sum() == 1

    def blk_fn():
        nl = n // shards
        off = jax.lax.axis_index("clients") * nl
        _, mask_blk = model.step_block(key, (), 0, off=off, n_local=nl,
                                       axis="clients")
        return mask_blk

    got = np.asarray(jax.jit(jax.shard_map(
        blk_fn, mesh=mesh, in_specs=(), out_specs=P("clients"),
        check_vma=False))())
    np.testing.assert_array_equal(got, np.asarray(full))
