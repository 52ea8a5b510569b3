"""Client-availability processes (paper §4.1) and communication constraints.

Every process produces, per round ``t``, a boolean availability mask
``A_t ∈ {0,1}^N`` and the communication budget ``K_t`` (max clients that may
be selected this round).  Together they realize the feasible-configuration
process ``C_t = {S ⊆ A_t : |S| ≤ K_t}`` of Assumption 1.

All samplers are pure functions of a JAX PRNG key so they can run on host or
inside jit.  The paper's five models (Always / Scarce / HomeDevice /
SmartPhones / Uneven) are reproduced exactly as specified in §4.1 and §D.4;
a Markov-modulated model exercises the correlated-availability regime of
Assumption 1 beyond i.i.d. sampling.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .keys import NONEMPTY
from .spans import collective_scope


def force_nonempty(mask: jnp.ndarray, q: jnp.ndarray,
                   key: jax.Array) -> jnp.ndarray:
    """Force a non-empty available set (paper assumes A_t ≠ ∅): if every
    client is down, wake one chosen uniformly at random among the clients
    with the highest marginal probability.

    The random tie-break matters: a plain ``argmax(q)`` would always wake
    client 0 under homogeneous marginals — a deterministic availability
    bias in exactly the scarce regimes where all-down rounds happen.  The
    ONE implementation serves every availability model (stateless samplers
    here, stateful models in ``sim/processes.py``) so the engines' parity
    guarantees cannot silently diverge.  ``key`` should be a *derived* key
    (``fold_in`` of the step key) so the common non-empty path consumes
    nothing from the main PRNG stream.
    """
    tie = jax.random.uniform(key, q.shape)
    idx = jnp.argmax(jnp.where(q >= q.max(), tie, -1.0))
    fallback = jnp.zeros_like(mask).at[idx].set(True)
    return jnp.where(mask.any(), mask, fallback)


def force_nonempty_block(mask_blk: jnp.ndarray, cand_blk: jnp.ndarray,
                         off, axis: str) -> jnp.ndarray:
    """Blockwise :func:`force_nonempty` for one shard of a client mesh.

    ``cand_blk`` is this shard's slice of the full-width candidate vector
    ``where(q >= q.max(), tie, -1)`` (out-of-range pad lanes forced to
    −1).  Reproduces the full-width result bitwise without materializing
    (N,) anywhere: per-shard (max, first-argmax) pairs reduce across the
    mesh with the same first-occurrence tie order as a global ``argmax``
    (shards are ordered by offset, ``argmax`` picks the first shard
    attaining the global max, and within a shard the first local index).
    """
    v = cand_blk.max()
    j = jnp.argmax(cand_blk).astype(jnp.int32)
    with collective_scope(axis):
        vs = jax.lax.all_gather(v, axis)                # (D,) tiny
        js = jax.lax.all_gather(off + j, axis)
    idx = js[jnp.argmax(vs)]
    n_blk = mask_blk.sum().astype(jnp.int32)
    with collective_scope(axis):
        nonempty = jax.lax.psum(n_blk, axis) > 0
    ids = off + jnp.arange(mask_blk.shape[0], dtype=jnp.int32)
    return jnp.where(nonempty, mask_blk, ids == idx)


@dataclasses.dataclass(frozen=True)
class AvailabilityProcess:
    """Base class: per-client marginal probabilities, possibly time-varying."""

    n_clients: int

    def probs(self, t: jnp.ndarray) -> jnp.ndarray:
        """Per-client availability probability at round ``t`` — shape (N,)."""
        raise NotImplementedError

    def sample(self, key: jax.Array, t: jnp.ndarray) -> jnp.ndarray:
        """Boolean availability mask A_t, guaranteed non-empty (paper assumes
        the available set is non-empty at every round)."""
        q = self.probs(t)
        mask = jax.random.bernoulli(key, q)
        return force_nonempty(mask, q, jax.random.fold_in(key, NONEMPTY))


@dataclasses.dataclass(frozen=True)
class Always(AvailabilityProcess):
    """Baseline: all clients always available."""

    def probs(self, t):
        return jnp.ones((self.n_clients,))

    def sample(self, key, t):
        return jnp.ones((self.n_clients,), dtype=bool)


@dataclasses.dataclass(frozen=True)
class Scarce(AvailabilityProcess):
    """I.i.d. homogeneous availability with probability q (paper: q = 0.2)."""

    q: float = 0.2

    def probs(self, t):
        return jnp.full((self.n_clients,), self.q)


@dataclasses.dataclass(frozen=True)
class HomeDevices(AvailabilityProcess):
    """q_k = T_k / max_j T_j with T_k ~ lognormal(0, sigma) (paper: 0.5)."""

    sigma: float = 0.5
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        t_k = rng.lognormal(mean=0.0, sigma=self.sigma, size=self.n_clients)
        object.__setattr__(self, "_q", jnp.asarray(t_k / t_k.max()))

    def probs(self, t):
        return self._q


@dataclasses.dataclass(frozen=True)
class SmartPhones(AvailabilityProcess):
    """Sine-modulated HomeDevices: q_{k,t} = f_t * q_k with
    f(t) = 0.4 sin(t) + 0.5 sampled at t = 2*pi*j/24 (paper §D.4, sigma=0.25)."""

    sigma: float = 0.25
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        t_k = rng.lognormal(mean=0.0, sigma=self.sigma, size=self.n_clients)
        object.__setattr__(self, "_q", jnp.asarray(t_k / t_k.max()))

    def probs(self, t):
        phase = 2.0 * jnp.pi * (jnp.asarray(t, jnp.float32) % 24) / 24.0
        f_t = 0.4 * jnp.sin(phase) + 0.5
        return f_t * self._q


@dataclasses.dataclass(frozen=True)
class Uneven(AvailabilityProcess):
    """Availability inversely proportional to dataset size: q_k ∝ 1/p_k."""

    p: tuple = ()  # client data fractions, length N
    q_max: float = 0.9

    def __post_init__(self):
        p = np.asarray(self.p, dtype=np.float64)
        inv = 1.0 / np.maximum(p, 1e-12)
        q = inv / inv.max() * self.q_max
        object.__setattr__(self, "_q", jnp.asarray(q, jnp.float32))

    def probs(self, t):
        return self._q


@dataclasses.dataclass(frozen=True)
class MarkovClusters(AvailabilityProcess):
    """Correlated availability: clients grouped into clusters, each cluster
    driven by a 2-state (up/down) Markov chain; within an up cluster each
    client is available i.i.d. with prob ``q_up``.  Satisfies Assumption 1
    (finite irreducible chain) with genuinely correlated availabilities.

    This model is *stateful*; use :meth:`step` which threads cluster state.
    """

    n_clusters: int = 4
    p_up_given_down: float = 0.3
    p_down_given_up: float = 0.1
    q_up: float = 0.9
    q_down: float = 0.05

    def init_state(self) -> jnp.ndarray:
        return jnp.ones((self.n_clusters,), dtype=bool)

    def cluster_of(self) -> jnp.ndarray:
        return jnp.arange(self.n_clients) % self.n_clusters

    def step(self, key: jax.Array, state: jnp.ndarray):
        k1, k1b, k2 = jax.random.split(key, 3)
        go_up = jax.random.bernoulli(k1, self.p_up_given_down, state.shape)
        go_down = jax.random.bernoulli(k1b, self.p_down_given_up, state.shape)
        new_state = jnp.where(state, ~go_down, go_up)
        q = jnp.where(new_state[self.cluster_of()], self.q_up, self.q_down)
        mask = jax.random.bernoulli(k2, q)
        mask = force_nonempty(mask, q, jax.random.fold_in(k2, NONEMPTY))
        return new_state, mask

    def probs(self, t):  # stationary marginal, for reporting only
        pi_up = self.p_up_given_down / (self.p_up_given_down + self.p_down_given_up)
        q = pi_up * self.q_up + (1 - pi_up) * self.q_down
        return jnp.full((self.n_clients,), q)


# ---------------------------------------------------------------------------
# Communication constraints K_t
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CommBudget:
    """Time-varying communication constraint ``K_t``.

    ``fixed`` reproduces the paper's main setting (M = 10 clients / round);
    ``jitter > 0`` draws K_t uniformly from [max(1, fixed-jitter),
    fixed+jitter] to exercise time-varying constraints.
    """

    fixed: int = 10
    jitter: int = 0

    def sample(self, key: jax.Array, t) -> jnp.ndarray:
        if self.jitter == 0:
            return jnp.asarray(self.fixed, jnp.int32)
        lo = max(1, self.fixed - self.jitter)
        hi = self.fixed + self.jitter
        return jax.random.randint(key, (), lo, hi + 1).astype(jnp.int32)


AVAILABILITY_REGISTRY = {
    "always": Always,
    "scarce": Scarce,
    "homedevices": HomeDevices,
    "smartphones": SmartPhones,
    "uneven": Uneven,
    "markov": MarkovClusters,
}


def make_availability(name: str, n_clients: int, p: Optional[np.ndarray] = None,
                      **kw) -> AvailabilityProcess:
    name = name.lower()
    if name not in AVAILABILITY_REGISTRY:
        raise KeyError(
            f"unknown availability model {name!r}; registered: "
            f"{sorted(AVAILABILITY_REGISTRY)}")
    if name == "uneven":
        assert p is not None, "Uneven availability needs client data fractions p"
        return Uneven(n_clients=n_clients, p=tuple(np.asarray(p).tolist()), **kw)
    return AVAILABILITY_REGISTRY[name](n_clients=n_clients, **kw)
