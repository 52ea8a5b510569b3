"""Fused selection kernel (``repro.kernels.fed_select``) vs the unfused
XLA pipeline: BIT-parity, not allclose.

The contract is stronger than the other kernels' tolerance checks: the
fused cut must reproduce ``core.selection._topk_mask``'s stable
``(score, id)`` tie-break exactly, the inlined EMA must match
``core.rates.update_rates`` bit-for-bit, and each weight rule must match
its ``core.aggregation`` spelling bit-for-bit — the engines treat
``select_impl="pallas"`` as a pure implementation swap (DESIGN.md §3.1),
so any float drift would show up as a diverged trajectory.

Float comparisons here go through ``tobytes()`` — ``assert_array_equal``
treats +0.0 == −0.0 and NaN == NaN, which is weaker than the contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation, selection
from repro.core.hfun import R_MIN
from repro.core.rates import RateState, update_rates
from repro.core.strategies import SelectCtx, make_strategy
from repro.kernels import fed_select as fs
from repro.kernels import ref


def assert_bitwise(got, want, msg=""):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape, \
        (msg, got.dtype, want.dtype, got.shape, want.shape)
    assert got.tobytes() == want.tobytes(), \
        f"{msg}: max abs diff {np.abs(got - want).max()}"


def _case(n, seed, ties=False, q=0.5):
    rng = np.random.default_rng(seed)
    if ties:                          # few distinct score levels -> heavy ties
        scores = rng.integers(0, 4, n).astype(np.float32)
    else:
        scores = rng.normal(size=n).astype(np.float32)
    return jnp.asarray(scores), jnp.asarray(rng.random(n) < q)


# ---------------------------------------------------------------------------
# The threshold reformulation == the stable-argsort cut, bit for bit.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [32, 100, 513])
@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("k", [0, 1, 7, 10_000])
def test_threshold_mask_matches_topk_mask(n, ties, k):
    scores, avail = _case(n, seed=n + k, ties=ties)
    want = selection._topk_mask(scores, avail, jnp.asarray(k, jnp.int32))
    got = ref.topk_threshold_mask(scores, avail, jnp.asarray(k, jnp.int32))
    assert_bitwise(got, want, f"n={n} ties={ties} k={k}")
    assert int(got.sum()) == min(k, int(avail.sum()))


def test_edge_cases_empty_and_full():
    scores = jnp.arange(16, dtype=jnp.float32)
    k8 = jnp.asarray(8, jnp.int32)
    none_avail = jnp.zeros(16, bool)
    all_avail = jnp.ones(16, bool)
    # nobody available -> empty cohort, regardless of k
    assert int(ref.topk_threshold_mask(scores, none_avail, k8).sum()) == 0
    assert int(fs.fed_select_mask(scores, none_avail, k8,
                                  interpret=True).sum()) == 0
    # k >= |available| -> everyone available is selected
    got = ref.topk_threshold_mask(scores, all_avail, jnp.asarray(99, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.ones(16, bool))
    # k = 0 -> empty cohort
    assert int(ref.topk_threshold_mask(scores, all_avail,
                                       jnp.asarray(0, jnp.int32)).sum()) == 0


def test_tie_break_is_lowest_id_first():
    # all scores equal: the stable cut takes the lowest available ids
    scores = jnp.zeros(12, jnp.float32)
    avail = jnp.asarray([0, 1, 1, 0, 1, 1, 1, 0, 1, 1, 1, 1], bool)
    got = np.asarray(ref.topk_threshold_mask(scores, avail,
                                             jnp.asarray(4, jnp.int32)))
    want = np.zeros(12, bool)
    want[[1, 2, 4, 5]] = True         # first four available ids
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Pallas interpreter == fused jnp reference == unfused pipeline, bit for bit.
# ---------------------------------------------------------------------------

def _select_inputs(n, seed=0):
    rng = np.random.default_rng(seed)
    scores, avail = _case(n, seed=seed + 1, ties=True)
    r = jnp.asarray(rng.random(n).astype(np.float32))
    p = jnp.asarray(rng.dirichlet(np.ones(n)).astype(np.float32))
    rw = jnp.asarray((rng.random(n) * 0.9 + 0.05).astype(np.float32))
    return scores, avail, r, p, rw


def _unfused(scores, avail, k, r, p, rw, *, beta, weight_mode):
    """The exact op sequence the XLA strategy path runs.

    Jitted by the caller: the parity contract holds between compiled
    programs (the engines jit both paths); an *eager* EMA can differ by
    1 ulp from any compiled spelling via FMA contraction.
    """
    mask = selection._topk_mask(scores, avail, k)
    new_r = update_rates(RateState(r=r, t=jnp.zeros((), jnp.int32)),
                         mask, beta).r
    if weight_mode == "unbiased":
        w = aggregation.unbiased_weights(p, jnp.maximum(new_r, R_MIN), mask)
    elif weight_mode == "unbiased_frozen":
        w = aggregation.unbiased_weights(p, rw, mask)
    elif weight_mode == "uniform":
        w = aggregation.uniform_weights(mask)
    else:
        w = aggregation.fedavg_weights(p, mask)
    return mask, new_r, w


@pytest.mark.parametrize("weight_mode", ref.SELECT_WEIGHT_MODES)
@pytest.mark.parametrize("n", [64, 100, 513])
def test_fed_select_bitwise_all_backends(weight_mode, n):
    scores, avail, r, p, rw = _select_inputs(n, seed=n)
    k = jnp.asarray(9, jnp.int32)
    beta = 1e-3
    r_weight = rw if weight_mode == "unbiased_frozen" else None
    unfused = jax.jit(_unfused, static_argnames=("beta", "weight_mode"))
    want = unfused(scores, avail, k, r, p, rw,
                   beta=beta, weight_mode=weight_mode)
    for interpret in (True, None):    # Pallas interpreter / autodetect (ref)
        got = fs.fed_select(scores, avail, k, r, p, beta,
                            weight_mode=weight_mode, r_weight=r_weight,
                            interpret=interpret)
        for name, g, w in zip(("mask", "new_r", "weights"), got, want):
            assert_bitwise(g, w, f"{weight_mode} n={n} "
                                 f"interpret={interpret} {name}")


@pytest.mark.parametrize("n", [100, 513])
def test_fed_select_mask_interpret_bitwise(n):
    scores, avail = _case(n, seed=n, ties=True)
    for k in (0, 3, n):
        kk = jnp.asarray(k, jnp.int32)
        want = selection._topk_mask(scores, avail, kk)
        got = fs.fed_select_mask(scores, avail, kk, interpret=True)
        assert_bitwise(got, want, f"n={n} k={k}")


def test_order_key_preserves_float_order():
    # the kernel's bisection compares int32 keys; they must order and tie
    # exactly as the backend's own f32 compares do (−0.0 == +0.0, ±inf,
    # the unavailable sentinel; denormals as the backend treats them)
    rng = np.random.default_rng(3)
    special = np.array([0.0, -0.0, 1e-45, -1e-45, np.inf, -np.inf,
                        ref.SELECT_NEG, 1.0, -1.0], np.float32)
    x = jnp.asarray(np.concatenate(
        [special, rng.normal(size=247).astype(np.float32)]))

    @jax.jit
    def orders(v):
        key = fs._order_key(v)
        return (key[:, None] < key[None, :], v[:, None] < v[None, :],
                key[:, None] == key[None, :], v[:, None] == v[None, :])

    key_lt, lt, key_eq, eq = orders(x)
    np.testing.assert_array_equal(np.asarray(key_lt), np.asarray(lt))
    np.testing.assert_array_equal(np.asarray(key_eq), np.asarray(eq))


# ---------------------------------------------------------------------------
# Strategy layer: select_impl="pallas" is a pure implementation swap.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["f3ast", "fixed_f3ast", "fedavg",
                                      "uniform", "poc"])
def test_strategy_select_impl_parity(strategy):
    n, m = 100, 10
    rng = np.random.default_rng(5)
    p = jnp.asarray(rng.dirichlet(np.ones(n)).astype(np.float32))
    outs = {}
    for impl in ("xla", "pallas"):
        strat = make_strategy(strategy, n, p, clients_per_round=m,
                              select_impl=impl)
        step = jax.jit(strat.select)   # engines run strategies compiled
        state = strat.init(n)
        key = jax.random.PRNGKey(0)
        masks, weights = [], []
        for t in range(5):
            key, k1, k2 = jax.random.split(key, 3)
            cell_rng = np.random.default_rng(100 + t)
            avail = jnp.asarray(cell_rng.random(n) < 0.5)
            ctx = None
            if strat.needs_losses:
                ctx = SelectCtx(losses=jnp.asarray(
                    cell_rng.random(n).astype(np.float32)))
            mask, w, state = step(state, k2, avail,
                                  jnp.asarray(m, jnp.int32), ctx)
            masks.append(np.asarray(mask))
            weights.append(np.asarray(w))
        rates = getattr(state, "rates", None)
        outs[impl] = (np.stack(masks), np.stack(weights),
                      None if rates is None else np.asarray(rates.r))
    np.testing.assert_array_equal(outs["xla"][0], outs["pallas"][0])
    assert_bitwise(outs["pallas"][1], outs["xla"][1], f"{strategy} weights")
    if outs["xla"][2] is not None:
        assert_bitwise(outs["pallas"][2], outs["xla"][2], f"{strategy} r_k")


# ---------------------------------------------------------------------------
# Validation / fail-fast.
# ---------------------------------------------------------------------------

def test_weight_mode_validation():
    scores, avail, r, p, _ = _select_inputs(32)
    k = jnp.asarray(4, jnp.int32)
    with pytest.raises(ValueError, match="weight_mode"):
        fs.fed_select(scores, avail, k, r, p, 1e-3, weight_mode="nope")
    with pytest.raises(ValueError, match="r_weight"):
        fs.fed_select(scores, avail, k, r, p, 1e-3,
                      weight_mode="unbiased_frozen")


def test_select_impl_validation():
    p = jnp.full(8, 1 / 8, jnp.float32)
    with pytest.raises(ValueError, match="select_impl"):
        make_strategy("f3ast", 8, p, clients_per_round=2,
                      select_impl="mosaic")


def test_runspec_rejects_pallas_with_mesh():
    from repro.sim import RunSpec
    with pytest.raises(ValueError, match="sharded"):
        RunSpec(select_impl="pallas", mesh_shape=(1,)).resolved()
    with pytest.raises(ValueError, match="select_impl"):
        RunSpec(select_impl="fast").resolved()


# ---------------------------------------------------------------------------
# The selection path that ran is reported, never silently swapped.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine,aggregation", [("device", "sync"),
                                                ("host", "sync"),
                                                ("device", "buffered")])
@pytest.mark.parametrize("override,want", [(None, "ref"),
                                           ("interpret", "interpret")])
def test_final_metrics_report_select_path(monkeypatch, engine, aggregation,
                                          override, want):
    from repro.sim import RunSpec, run_scenario
    monkeypatch.setattr(fs, "AUTODETECT_OVERRIDE", override)
    silent = lambda *a, **k: None      # noqa: E731
    for impl, expect in (("xla", "xla"), ("pallas", want)):
        spec = RunSpec(scenario="scarce", rounds=2, eval_every=2,
                       engine=engine, aggregation=aggregation,
                       select_impl=impl)
        res = run_scenario(spec, log_fn=silent)
        assert res.final_metrics["select_path"] == expect, (impl, engine)


def test_dispatch_mode_reports_the_reference_beyond_the_kernel_cap(
        monkeypatch):
    # on a TPU the compiled kernel runs up to MAX_KERNEL_N; past it the
    # fused reference runs, and dispatch_mode says so
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fs.dispatch_mode(fs.MAX_KERNEL_N) == "compiled"
    assert fs.dispatch_mode(fs.MAX_KERNEL_N + 1) == "ref"
    assert fs.dispatch_mode(10, interpret=True) == "interpret"
