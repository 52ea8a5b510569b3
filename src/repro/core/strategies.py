"""Pluggable client-selection strategies: protocol + string registry.

The paper's contribution is a *selection policy* (Algorithm 1) evaluated
against baselines; this module makes a policy one registry entry instead of
an ``if/elif`` branch inside every engine.  A strategy is a pair of pure
functions in the optax ``GradientTransformation`` style:

    init(n_clients, r0=None) -> state          # an arbitrary pytree
    select(state, key, avail, k_t, ctx) -> (mask, weights, new_state)

``mask``/``weights`` are full (N,) arrays (weights zero off-cohort), so the
engines stay strategy-agnostic: the host loop, the device-resident scan
engine, and the client-sharded engine all call the same ``select``.

Most policies are "score the available clients, keep the top K_t, weight
the winners" — build those with :func:`topk_strategy` from a ``score`` and
a ``finalize`` piece.  Strategies built that way additionally get the
client-sharded engine for free: :func:`as_sharded` wraps the same pieces
around the distributed top-k (``selection.sharded_topk_mask``), computing
the (cheap, O(N)-elementwise) scores and weights replicated at full shape
so the selected set is bit-identical to the single-device path.

Registry:

    register_strategy("my_policy", factory)     # or use as a decorator
    strategy = make_strategy("my_policy", n_clients, p, beta=1e-3)

A factory is ``f(n_clients, p, **hyperparams) -> SelectionStrategy``;
:func:`make_strategy` passes only the hyperparameters the factory accepts,
so engine-supplied defaults (``beta``, ``clients_per_round``, ...) never
break a custom factory that ignores them.  Aliases (``fedadam`` = fedavg
selection + Adam server) resolve in :func:`resolve_strategy` — ONE place,
before any engine dispatch, so every engine sees the same resolved name.

Built-in strategies
  f3ast            greedy −∇H(r) top-K (Alg. 1)     weights p_k/r_k (unbiased)
  fixed_f3ast      Alg. 2, frozen target rate        weights p_k/r_k(target)
  fedavg           sample ∝ p_k over available       weights 1/|S|  (biased)
  fedavg_weighted  sample ∝ p_k over available       weights ∝ p_k  (biased)
  uniform          uniform over available            weights 1/|S|  (biased)
  poc              Power-of-Choice (host-only: needs fresh per-client losses)
"""
from __future__ import annotations

import inspect
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import selection as sel
from .aggregation import fedavg_weights, unbiased_weights, uniform_weights
from .bitmask import all_gather_bits
from .hfun import R_MIN, marginal_utility
from .rates import RateState, init_rates, update_rates

__all__ = [
    "SELECT_IMPLS", "STRATEGY_ALIASES", "STRATEGY_REGISTRY", "RateTrackState",
    "SelectCtx", "SelectionStrategy", "StrategyAlias", "apply_completion",
    "as_sharded", "get_strategy_entry", "list_strategies", "make_strategy",
    "register_strategy", "resolve_strategy", "strategy_rates",
    "topk_strategy",
]

# Top-k cut implementations a strategy can run on (RunSpec.select_impl):
#   "xla"    — selection._topk_mask (argsort + scatter), the reference
#   "pallas" — kernels.fed_select: the fused cut (+ EMA + weights when no
#              completion hook splits the pipeline); on TPU a compiled
#              Pallas kernel, elsewhere the fused jnp reference.
# The sharded mesh engine always uses selection.sharded_topk_mask — RunSpec
# validation rejects select_impl="pallas" with mesh set.
SELECT_IMPLS = ("xla", "pallas")


def _check_select_impl(select_impl: str) -> str:
    if select_impl not in SELECT_IMPLS:
        raise ValueError(f"unknown select_impl {select_impl!r}; "
                         f"known: {SELECT_IMPLS}")
    return select_impl


def select_path(select_impl: str, n_clients: int) -> str:
    """The top-k cut that ``select_impl`` runs over ``n_clients``:
    ``"xla"``, or the fused kernel's dispatch — ``"compiled"`` (Mosaic on
    TPU), ``"interpret"`` or ``"ref"`` (fused jnp reference, off-TPU or
    beyond ``MAX_KERNEL_N``).  The engines report it as
    ``final_metrics["select_path"]``."""
    if _check_select_impl(select_impl) == "pallas":
        from ..kernels.fed_select import dispatch_mode
        return dispatch_mode(n_clients)
    return "xla"


def _topk_fn(select_impl: str):
    """The (scores, avail, k) -> mask cut for ``select_impl`` — bit-identical
    outputs either way (tests/test_kernels_select.py)."""
    if select_impl == "pallas":
        from ..kernels.fed_select import fed_select_mask
        return fed_select_mask
    return sel._topk_mask


class SelectCtx(NamedTuple):
    """Per-round side inputs a strategy may consume (all optional).

    ``complete`` is the engine's completion hook — a pure function
    ``(N,) selection mask -> (N,) completed mask`` closing over the
    round's derived completion key (``repro.sim.completion``).  Strategies
    apply it via :func:`apply_completion` between selection and
    ``finalize`` so the rate EMA and aggregation weights are driven by the
    clients that actually *returned* an update, not merely the selected
    ones.  ``None`` (no completion process, or ``completion="always"``)
    means selected == completed.
    """
    t: Optional[jnp.ndarray] = None        # round index
    losses: Optional[jnp.ndarray] = None   # (N,) fresh per-client losses
    complete: Optional[Callable] = None    # sel mask (N,) -> completed (N,)


def apply_completion(ctx: Optional["SelectCtx"],
                     mask: jnp.ndarray) -> jnp.ndarray:
    """Completed mask from the engine's completion hook (identity without
    one).  Pure and deterministic given the hook's captured key, so engines
    recompute the same mask for streaming/zero-weighting."""
    if ctx is None or ctx.complete is None:
        return mask
    return ctx.complete(mask)


class RateTrackState(NamedTuple):
    """State of the built-in strategies: the Alg. 1 line-5 rate EMA."""
    rates: RateState


class SelectionStrategy(NamedTuple):
    """A selection policy as pure functions (optax-style).

    ``init(n_clients, r0=None) -> state`` and
    ``select(state, key, avail, k_t, ctx) -> (mask, weights, new_state)``
    are the whole protocol; engines never look inside ``state`` (any pytree
    works — it is not hardwired to :class:`RateTrackState`).

    ``score``/``finalize`` are the optional top-k decomposition (see
    :func:`topk_strategy`) that :func:`as_sharded` needs; ``rates_of``
    optionally extracts a tracked (N,) participation rate for reporting;
    ``needs_losses``/``host_only`` route the strategy to the host loop.

    ``score_block(state, key, avail_blk, k_t, ctx, off, n_total) ->
    (n_local,) f32`` is an optional blockwise spelling of ``score`` for
    the sharded engine: the slice ``[off, off + n_local)`` of the
    full-width score vector, bitwise-identical to computing and slicing
    it (random tie-breaks via the slice-consistent ``core.blockrng``
    draws), at O(n_local) per-shard cost with no (N,) intermediate.
    Out-of-range pad lanes must score 0 (matching the adapter's zero-pad
    of the full-width path).  Strategies without it still run sharded
    through the full-width ``score`` + slice.
    """
    name: str
    init: Callable[..., Any]
    select: Callable[..., Any]
    score: Optional[Callable[..., Any]] = None
    finalize: Optional[Callable[..., Any]] = None
    rates_of: Optional[Callable[[Any], Any]] = None
    n_clients: Optional[int] = None
    needs_losses: bool = False
    host_only: bool = False
    score_block: Optional[Callable[..., Any]] = None


def strategy_rates(strategy: SelectionStrategy, state):
    """Tracked (N,) participation rates of ``state``, or None.

    Uses ``strategy.rates_of`` when provided, else the built-in state
    convention ``state.rates.r``.
    """
    if strategy.rates_of is not None:
        return strategy.rates_of(state)
    return getattr(getattr(state, "rates", None), "r", None)


def topk_strategy(name: str, init: Callable, score: Callable,
                  finalize: Callable, *, n_clients: Optional[int] = None,
                  rates_of: Optional[Callable] = None,
                  select_impl: str = "xla",
                  fused: Optional[Callable] = None,
                  score_block: Optional[Callable] = None
                  ) -> SelectionStrategy:
    """Build a strategy from the canonical score → top-k → weight shape.

    ``score(state, key, avail, k_t, ctx) -> (N,) f32`` ranks clients;
    the top ``min(k_t, |avail|)`` available ones are selected
    (``selection._topk_mask`` — stable (score, id) tie-break);
    ``finalize(state, mask, ctx) -> (weights (N,), new_state)`` assigns
    aggregation weights and advances the state.  ``finalize`` receives the
    *completed* mask (selected clients that survived the round's
    completion process — identical to the selection mask when no
    completion hook is active), so rate EMAs count deliveries and weights
    renormalize over survivors; the selection mask is what ``select``
    returns to the engine.  Strategies built this way run on all three
    engines — :func:`as_sharded` reuses the same two pieces around the
    distributed top-k.

    ``select_impl`` swaps the top-k cut: ``"xla"`` (default) is the argsort
    path, ``"pallas"`` the fused ``kernels.fed_select`` kernel —
    bit-identical masks either way.  ``fused(state, scores, avail, k_t) ->
    (mask, weights, new_state)`` is the optional fully-fused spelling of
    cut + ``finalize`` in one kernel pass (see :func:`_fused_rate_select`);
    it is used only under ``select_impl="pallas"`` with no completion hook
    in play — a completion process rewrites the mask between cut and
    ``finalize``, which cannot fuse, so those rounds take the fused cut +
    unfused ``finalize`` instead.  Custom strategies may omit ``fused`` and
    still get the kernel cut.
    """
    _check_select_impl(select_impl)
    topk = _topk_fn(select_impl)
    use_fused = select_impl == "pallas" and fused is not None

    def select(state, key, avail, k_t, ctx: Optional[SelectCtx] = None):
        scores = score(state, key, avail, k_t, ctx)
        if use_fused and (ctx is None or ctx.complete is None):
            return fused(state, scores, avail, k_t)
        mask = topk(scores, avail, k_t)
        completed = apply_completion(ctx, mask)
        weights, new_state = finalize(state, completed, ctx)
        return mask, weights, new_state

    return SelectionStrategy(name=name, init=init, select=select,
                             score=score, finalize=finalize,
                             rates_of=rates_of, n_clients=n_clients,
                             score_block=score_block)


def _fused_rate_select(p, beta: float, weight_mode: str,
                       r_weight_of: Optional[Callable] = None) -> Callable:
    """Fully-fused select for the built-in :class:`RateTrackState`
    strategies: one ``kernels.fed_select`` call yields mask, the Alg. 1
    line-5 rate EMA, and the line-9 weights — bit-identical to the unfused
    cut → ``update_rates`` → weight-rule pipeline (the fused-vs-unfused
    cells of the parity matrix assert it).  ``r_weight_of(state)`` supplies
    the frozen rate for ``weight_mode="unbiased_frozen"`` (Alg. 2)."""
    from ..kernels.fed_select import fed_select

    def fused(state, scores, avail, k_t):
        rw = None if r_weight_of is None else r_weight_of(state)
        mask, new_r, w = fed_select(scores, avail, k_t, state.rates.r, p,
                                    beta, weight_mode=weight_mode,
                                    r_weight=rw)
        new_state = RateTrackState(
            rates=RateState(r=new_r, t=state.rates.t + 1))
        return mask, w, new_state

    return fused


def as_sharded(strategy: SelectionStrategy, *, axis: str, k_max: int,
               n_pad: int, topk_impl: str = "stream") -> Callable:
    """Generic blockwise adapter for the client-sharded engine.

    Returns ``select_blk(state, key, avail_blk, k_t, ctx, avail_full=None)
    -> (mask_blk, weights_blk, new_state, completed_full)`` for use inside
    ``shard_map`` over ``axis``: ``avail_blk`` is this shard's block of the
    client dimension padded to ``n_pad``; the strategy ``state`` is
    replicated (full real-N shape on every shard).  Scores and weights are
    computed at full (N,) shape from the strategy's own
    ``score``/``finalize`` — identical computation, same key ⇒ same values
    as the single-device path — and only the top-k cut is distributed
    (``selection.sharded_topk_mask``, bit-identical tie-break), so the
    assembled global mask and the state trajectory match the unsharded
    engine exactly.  Recomputing the O(N) elementwise fields replicated is
    deliberate: they are a few hundred KB at N = 100k, while the staged
    data, availability state, and the top-k sort stay sharded.

    Callers that already hold the replicated full-width availability mask
    (the sharded engine steps the availability process at (N,) shape on
    every shard) pass it as ``avail_full`` to skip the gather; otherwise it
    is reassembled from ``avail_blk``.  ``completed_full`` is the
    replicated full-width completed mask (identity to the selection mask
    without a completion hook) — returned so the engine never re-gathers
    or re-draws it.

    ``topk_impl`` (``RunSpec.topk_impl``) picks the distributed cut's
    reduction — ``"stream"`` (ppermute candidate merging, the default) or
    ``"allgather"`` (the reference full-candidate gather) — bit-identical
    masks either way.  The full-width bool gathers of the availability and
    selection masks move bit-packed uint32 words when the shard block is
    32-divisible (``core.bitmask``; the staging paths pad the client dim
    to guarantee it), an 8× cut of the per-round mask traffic.
    """
    if strategy.score is None or strategy.finalize is None:
        raise ValueError(
            f"strategy {strategy.name!r} has no score/finalize "
            f"decomposition, so the generic sharded adapter cannot run it; "
            f"build it with topk_strategy(...) or use an unsharded engine")
    n = strategy.n_clients
    if n is None:
        raise ValueError(f"strategy {strategy.name!r} does not declare "
                         f"n_clients; as_sharded needs it to un-pad fields")
    if topk_impl not in sel.TOPK_IMPLS:
        raise ValueError(f"unknown topk_impl {topk_impl!r}; "
                         f"known: {sel.TOPK_IMPLS}")

    def pad(x):
        return jnp.pad(x, [(0, n_pad - x.shape[0])]
                       + [(0, 0)] * (x.ndim - 1))

    def select_blk(state, key, avail_blk, k_t,
                   ctx: Optional[SelectCtx] = None, avail_full=None):
        n_local = avail_blk.shape[0]
        off = jax.lax.axis_index(axis) * n_local
        if strategy.score_block is not None:
            # O(n_local) blockwise score — no (N,) intermediate, no
            # availability gather (bitwise-identical by contract)
            scores_blk = strategy.score_block(state, key, avail_blk, k_t,
                                              ctx, off, n)
        else:
            if avail_full is None:
                avail_full = all_gather_bits(avail_blk, axis, n)
            scores = strategy.score(state, key, avail_full, k_t, ctx)
            scores_blk = jax.lax.dynamic_slice_in_dim(pad(scores), off,
                                                      n_local)
        mask_blk = sel.sharded_topk_mask(scores_blk, avail_blk, k_t, axis,
                                         k_max, method=topk_impl)
        mask_full = all_gather_bits(mask_blk, axis, n)
        # completion draws at full (N,) shape from the replicated key —
        # identical on every shard and to the single-device path
        completed_full = apply_completion(ctx, mask_full)
        weights, new_state = strategy.finalize(state, completed_full, ctx)
        w_blk = jax.lax.dynamic_slice_in_dim(
            pad(weights.astype(jnp.float32)), off, n_local)
        return mask_blk, w_blk, new_state, completed_full

    return select_blk


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

class StrategyEntry(NamedTuple):
    factory: Callable[..., SelectionStrategy]
    host_only: bool = False
    needs_losses: bool = False


class StrategyAlias(NamedTuple):
    """A convenience name = strategy + server-optimizer defaults."""
    strategy: str
    server_opt: Optional[str] = None
    server_lr: Optional[float] = None


STRATEGY_REGISTRY: Dict[str, StrategyEntry] = {}

# FedAdam (Reddi et al. / paper §4) = FedAvg selection + Adam server step.
STRATEGY_ALIASES: Dict[str, StrategyAlias] = {
    "fedadam": StrategyAlias("fedavg", server_opt="adam", server_lr=1e-2),
}


def register_strategy(name: str, factory: Optional[Callable] = None, *,
                      host_only: bool = False, needs_losses: bool = False,
                      overwrite: bool = False):
    """Register ``factory(n_clients, p, **hyper) -> SelectionStrategy``.

    Usable as a decorator.  ``host_only`` keeps the strategy off the
    compiled engines (``run_scenario`` falls back to the host loop with a
    warning); ``needs_losses`` asks the host loop for fresh per-client
    losses in ``ctx.losses`` each round (implies host-only execution).
    """

    def deco(f):
        key = name.lower()
        if not overwrite and key in STRATEGY_REGISTRY:
            raise KeyError(f"strategy {key!r} already registered")
        STRATEGY_REGISTRY[key] = StrategyEntry(
            factory=f, host_only=host_only or needs_losses,
            needs_losses=needs_losses)
        return f

    return deco(factory) if factory is not None else deco


def list_strategies() -> list:
    return sorted(STRATEGY_REGISTRY)


def get_strategy_entry(name: str) -> StrategyEntry:
    """Registry lookup that fails fast with the registered names."""
    key = str(name).lower()
    if key not in STRATEGY_REGISTRY:
        raise KeyError(
            f"unknown selection strategy {name!r}; registered: "
            f"{list_strategies()} (aliases: {sorted(STRATEGY_ALIASES)})")
    return STRATEGY_REGISTRY[key]


def resolve_strategy(name: str, server_opt: str = "sgd",
                     server_lr: Optional[float] = None):
    """Resolve aliases + server-optimizer defaults in ONE place.

    Returns ``(strategy_name, server_opt, server_lr)``: aliases such as
    ``fedadam`` rewrite to their base strategy and pin the server
    optimizer; ``server_lr=None`` then fills with the optimizer's default
    (1e-2 for adam/yogi, else 1.0).  Every entry point (host loop, device
    engine, sharded engine, CLIs) calls this before dispatch, so no engine
    ever sees an unresolved alias.  Unknown names raise ``KeyError`` here —
    before anything compiles.
    """
    key = str(name).lower()
    if key in STRATEGY_ALIASES:
        alias = STRATEGY_ALIASES[key]
        key = alias.strategy
        if alias.server_opt is not None:
            server_opt = alias.server_opt
        if server_lr is None and alias.server_lr is not None:
            server_lr = alias.server_lr
    get_strategy_entry(key)
    if server_lr is None:
        server_lr = 1e-2 if server_opt in ("adam", "yogi") else 1.0
    return key, server_opt, server_lr


# keys every engine passes by default; factories may ignore them, so they
# alone are dropped silently when a factory's signature lacks them
_ENGINE_DEFAULT_KEYS = frozenset(
    {"beta", "positively_correlated", "clients_per_round", "select_impl"})


def make_strategy(name: str, n_clients: int, p, **hyper) -> SelectionStrategy:
    """Instantiate a registered strategy for (n_clients, p).

    Of the hyperparameters not accepted by the factory's signature, only
    the engine-supplied standard set (``beta``, ``positively_correlated``,
    ``clients_per_round``) is dropped silently — engines can always offer
    those without constraining custom factories.  Any *other* unaccepted
    key (e.g. a typo in ``RunSpec.strategy_kwargs``) raises ``TypeError``
    — fail fast, never run with a silently-ignored hyperparameter.
    """
    entry = get_strategy_entry(name)
    params = inspect.signature(entry.factory).parameters
    if not any(q.kind == q.VAR_KEYWORD for q in params.values()):
        unknown = set(hyper) - set(params) - _ENGINE_DEFAULT_KEYS
        if unknown:
            accepted = sorted(set(params) - {"n_clients", "p"})
            raise TypeError(
                f"strategy {name!r} factory does not accept "
                f"{sorted(unknown)}; its hyperparameters are {accepted}")
        hyper = {k: v for k, v in hyper.items() if k in params}
    strategy = entry.factory(n_clients=n_clients,
                             p=jnp.asarray(p, jnp.float32), **hyper)
    # registry-level routing flags apply even when the factory (e.g. one
    # built with topk_strategy) did not set them on the instance — the host
    # loop reads the instance flags to decide on fresh-loss computation
    if ((entry.needs_losses and not strategy.needs_losses)
            or (entry.host_only and not strategy.host_only)):
        strategy = strategy._replace(
            needs_losses=strategy.needs_losses or entry.needs_losses,
            host_only=strategy.host_only or entry.host_only)
    return strategy


# ---------------------------------------------------------------------------
# Built-in strategies
# ---------------------------------------------------------------------------

def _calibrated_r0(n_clients: int, r0, clients_per_round) -> float:
    """Default rate-EMA init r(0) (Algorithm 1 line 1: "arbitrary").

    Explicit ``r0`` wins; otherwise the calibrated uniform feasible rate
    K/N (shortens the stochastic-approximation burn-in, Thm B.1) when the
    expected cohort size is known; the constant 0.1 is the explicit
    fallback when it is not.
    """
    if r0 is not None:
        return r0
    if clients_per_round:
        return min(1.0, clients_per_round / n_clients)
    return 0.1


def _rate_init(n_default: int, clients_per_round) -> Callable:
    def init(n_clients: int = n_default, r0=None):
        return RateTrackState(rates=init_rates(
            n_clients, _calibrated_r0(n_clients, r0, clients_per_round)))
    return init


def _ema_finalize(beta: float, weights_from_mask: Callable) -> Callable:
    """finalize = rate-EMA step + a weights rule on the *pre-update* state.

    ``mask`` here is the completed mask (== the selection mask when no
    completion process is active): the EMA counts deliveries, and the
    weights rule renormalizes over the surviving cohort.
    """

    def finalize(state, mask, ctx=None):
        new_rates = update_rates(state.rates, mask, beta)
        return weights_from_mask(mask), RateTrackState(rates=new_rates)

    return finalize


def _rate_score_block(p, positively_correlated: bool,
                      r_of: Callable) -> Callable:
    """Blockwise spelling of the rate-utility score (f3ast family): the
    slice of ``marginal_utility(r, p) * (1 + 1e-6·uniform)`` computed from
    the block's own r/p rows and the slice-consistent ``core.blockrng``
    tie-break — bitwise-identical to slicing the full-width score, pad
    lanes 0 (matching the sharded adapter's zero-pad)."""
    from .blockrng import block_uniform
    p_arr = jnp.asarray(p, jnp.float32)

    def score_block(state, key, avail_blk, k_t, ctx, off, n_total):
        n_local = avail_blk.shape[0]
        ids = off + jnp.arange(n_local, dtype=jnp.int32)
        real = ids < n_total
        safe = jnp.minimum(ids, n_total - 1)
        r_blk = jnp.take(r_of(state), safe)
        p_blk = jnp.take(p_arr, safe)
        util = marginal_utility(r_blk, p_blk, positively_correlated)
        tie = block_uniform(key, n_total, off, n_local)
        return jnp.where(real, util * (1.0 + 1e-6 * tie), 0.0)

    return score_block


@register_strategy("f3ast")
def _make_f3ast(n_clients, p, beta: float = 1e-3,
                positively_correlated: bool = False,
                clients_per_round: Optional[int] = None,
                select_impl: str = "xla") -> SelectionStrategy:
    """Algorithm 1: greedy −∇H(r) selection, unbiased p_k/r_k weights."""

    def score(state, key, avail, k_t, ctx=None):
        util = marginal_utility(state.rates.r, p, positively_correlated)
        # Infinitesimal random tie-break so identical utilities (e.g. at
        # initialization with uniform r) do not favor low-index clients.
        return util * (1.0 + 1e-6 * jax.random.uniform(key, util.shape))

    def finalize(state, mask, ctx=None):
        # Alg. 1: select with r(t−1) (line 4), update the EMA (line 5),
        # aggregate with the *updated* r(t) (line 9).
        new_rates = update_rates(state.rates, mask, beta)
        w = unbiased_weights(p, jnp.maximum(new_rates.r, R_MIN), mask)
        return w, RateTrackState(rates=new_rates)

    return topk_strategy("f3ast", _rate_init(n_clients, clients_per_round),
                         score, finalize, n_clients=n_clients,
                         select_impl=select_impl,
                         fused=_fused_rate_select(p, beta, "unbiased"),
                         score_block=_rate_score_block(
                             p, positively_correlated,
                             lambda s: s.rates.r))


@register_strategy("fixed_f3ast")
def _make_fixed_f3ast(n_clients, p, beta: float = 1e-3,
                      positively_correlated: bool = False, r_target=None,
                      clients_per_round: Optional[int] = None,
                      select_impl: str = "xla") -> SelectionStrategy:
    """Algorithm 2: greedy w.r.t. a *frozen* target rate (falls back to the
    tracked r(t−1) when no target is given)."""
    rt_fixed = None if r_target is None else jnp.asarray(r_target, jnp.float32)

    def score(state, key, avail, k_t, ctx=None):
        rt = rt_fixed if rt_fixed is not None else state.rates.r
        util = marginal_utility(rt, p, positively_correlated)
        # Same infinitesimal random tie-break as f3ast: under a uniform
        # (target) rate every utility ties, and the stable (score, id)
        # tie-break would deterministically select the lowest-index
        # clients round after round.
        return util * (1.0 + 1e-6 * jax.random.uniform(key, util.shape))

    def finalize(state, mask, ctx=None):
        rt = rt_fixed if rt_fixed is not None else state.rates.r
        w = unbiased_weights(p, jnp.maximum(rt, R_MIN), mask)
        return w, RateTrackState(rates=update_rates(state.rates, mask, beta))

    return topk_strategy("fixed_f3ast",
                         _rate_init(n_clients, clients_per_round),
                         score, finalize, n_clients=n_clients,
                         select_impl=select_impl,
                         fused=_fused_rate_select(
                             p, beta, "unbiased_frozen",
                             r_weight_of=lambda s: (
                                 rt_fixed if rt_fixed is not None
                                 else s.rates.r)),
                         score_block=_rate_score_block(
                             p, positively_correlated,
                             lambda s: (rt_fixed if rt_fixed is not None
                                        else s.rates.r)))


def _gumbel_score(p):
    """log p + Gumbel: top-k ⇔ sampling w/o replacement ∝ p_k."""

    def score(state, key, avail, k_t, ctx=None):
        g = jax.random.gumbel(key, p.shape)
        return jnp.log(jnp.maximum(p, 1e-12)) + g

    return score


@register_strategy("fedavg")
def _make_fedavg(n_clients, p, beta: float = 1e-3,
                 clients_per_round: Optional[int] = None,
                 select_impl: str = "xla") -> SelectionStrategy:
    """Paper baseline: sample available clients ∝ p_k, plain-mean
    aggregation (Li et al. scheme II) — biased under intermittent
    availability, which is the failure mode F3AST's reweighting removes."""
    return topk_strategy("fedavg", _rate_init(n_clients, clients_per_round),
                         _gumbel_score(p),
                         _ema_finalize(beta, uniform_weights),
                         n_clients=n_clients, select_impl=select_impl,
                         fused=_fused_rate_select(p, beta, "uniform"))


@register_strategy("fedavg_weighted")
def _make_fedavg_weighted(n_clients, p, beta: float = 1e-3,
                          clients_per_round: Optional[int] = None,
                          select_impl: str = "xla") -> SelectionStrategy:
    return topk_strategy("fedavg_weighted",
                         _rate_init(n_clients, clients_per_round),
                         _gumbel_score(p),
                         _ema_finalize(beta,
                                       lambda mask: fedavg_weights(p, mask)),
                         n_clients=n_clients, select_impl=select_impl,
                         fused=_fused_rate_select(p, beta, "fedavg"))


@register_strategy("uniform")
def _make_uniform(n_clients, p, beta: float = 1e-3,
                  clients_per_round: Optional[int] = None,
                  select_impl: str = "xla") -> SelectionStrategy:
    def score(state, key, avail, k_t, ctx=None):
        return jax.random.uniform(key, avail.shape)

    return topk_strategy("uniform", _rate_init(n_clients, clients_per_round),
                         score, _ema_finalize(beta, uniform_weights),
                         n_clients=n_clients, select_impl=select_impl,
                         fused=_fused_rate_select(p, beta, "uniform"))


@register_strategy("poc", needs_losses=True)
def _make_poc(n_clients, p, beta: float = 1e-3, d: int = 30,
              clients_per_round: Optional[int] = None,
              select_impl: str = "xla") -> SelectionStrategy:
    """Power-of-Choice (Cho et al.): d candidates ∝ p_k, keep the top
    K_t by current local loss.  Host-only: the two-stage draw consumes
    fresh per-client losses the compiled engines do not have."""
    topk = _topk_fn(_check_select_impl(select_impl))

    def select(state, key, avail, k_t, ctx: Optional[SelectCtx] = None):
        losses = None if ctx is None else ctx.losses
        if losses is None:
            raise ValueError("'poc' needs ctx.losses (fresh per-client "
                             "losses of the current global model)")
        mask = sel.poc_select(key, avail, k_t, p, losses, d, topk=topk)
        completed = apply_completion(ctx, mask)
        new_rates = update_rates(state.rates, completed, beta)
        return (mask, uniform_weights(completed),
                RateTrackState(rates=new_rates))

    return SelectionStrategy(name="poc",
                             init=_rate_init(n_clients, clients_per_round),
                             select=select, n_clients=n_clients,
                             needs_losses=True, host_only=True)
