"""Plain reference of the federated round, written from the paper and the
configuration, importing nothing of the system.

Per round t, from the run's key (split five ways: carry, availability,
selection, budget, batch):

1. availability: client k is up with its marginal q_k (one Bernoulli draw
   per client); if nobody is up, one of the clients with the highest q_k
   is woken, chosen by a uniform draw off the availability key;
2. budget: K_t = k (constant schedule);
3. F3AST selection (paper Algorithm 1 line 4): the min(K_t, |A_t|)
   available clients with the largest p_k^2 / max(r_k, 1e-3)^2, each
   scaled by (1 + 1e-6 u_k) with u_k uniform off the selection key; equal
   scores go to the lower client id;
4. rate EMA (line 5): r <- (1 - beta) r + beta 1_S;
5. cohort: the selected ids in ascending order, padded to k slots with the
   first id (padded slots get weight 0 but train, and their loss counts);
   each slot's E minibatches of B samples drawn uniformly from the client's
   samples off the batch key;
6. local SGD (lines 6-8): E steps of w <- w - lr grad on each client,
   the slots one after another;
7. aggregation (line 9): Delta = sum_k (p_k / max(r_k, 1e-3)) (w_k - w),
   with r after step 4, summed in slot order; server SGD:
   w <- w + server_lr Delta.

The round's loss is the mean over the k slots of each slot's mean loss
over its E steps.  Matrix products run at ``highest`` precision, or, with
``one_pass``, as a TPU's default float32 product, forward and backward:
operands rounded to bfloat16, float32 accumulation (a stand-in for the
system on the chip where no chip is had).  The model side (weights, data,
local SGD, aggregation) runs in ``dtype`` and the selection side (rates,
scores, weights) in ``select_dtype``, which defaults to ``dtype``: the
same code in bfloat16 is the control.  The random draws are the same bits
in every dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

R_MIN = 1e-3
_NEG = np.float32(-1e30)


def availability_marginals(traffic: dict, n: int) -> np.ndarray:
    """(N,) f32 marginal availability q_k of the mix's process."""
    name, kw = traffic["availability"], dict(traffic["availability_kwargs"])
    if name == "scarce":
        return np.full(n, kw.get("q", 0.2), np.float32)
    if name == "bernoulli":
        q, sigma = kw.get("q", 0.5), kw.get("sigma", 0.0)
        if sigma > 0:
            t = np.random.default_rng(kw.get("seed", 0)).lognormal(0.0, sigma, n)
            return (q * t / t.max()).astype(np.float32)
        return np.full(n, q, np.float32)
    if name == "homedevices":
        t = np.random.default_rng(kw.get("seed", 0)).lognormal(
            0.0, kw.get("sigma", 0.5), n)
        return (t / t.max()).astype(np.float32)
    raise NotImplementedError(f"no reference for availability {name!r}")


def _budget(traffic: dict) -> int:
    if traffic["budget"] != "constant" or traffic["strategy"] != "f3ast":
        raise NotImplementedError("the reference covers the constant budget "
                                  "and F3AST selection")
    return int(traffic["budget_kwargs"]["k"])


def _bf16_pass(a, b):
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


@jax.custom_vjp
def one_pass_dot(a, b):
    """``a @ b`` (``b`` a matrix) in one bfloat16 pass, and so its
    gradients."""
    return _bf16_pass(a, b)


def _one_pass_fwd(a, b):
    return _bf16_pass(a, b), (a, b)


def _one_pass_bwd(res, g):
    a, b = res
    return (_bf16_pass(g, b.T),
            _bf16_pass(a.reshape(-1, a.shape[-1]).T, g.reshape(-1, g.shape[-1])))


one_pass_dot.defvjp(_one_pass_fwd, _one_pass_bwd)


def run_reference(cell, inputs, seed: int, rounds: int, dtype=jnp.float32,
                  select_dtype=None, one_pass=False) -> dict:
    """``rounds`` rounds from round 0 for run seed ``seed``."""
    with jax.default_matmul_precision("highest"):
        return _run(cell, inputs, seed, rounds, dtype, select_dtype or dtype,
                    one_pass_dot if one_pass else jnp.dot)


def cohort_train(cfg, loss, dtype):
    """One round's local SGD, aggregation and server step, jitted with
    ``params`` donated: ``train(params, batch, w)`` -> (new params, round
    loss, |Delta|), with ``batch`` holding the k slots' E minibatches and
    ``w`` their aggregation weights.  The slots train one after another
    (a ``lax.scan`` in slot order), each from ``params``, and Delta is
    summed in an accumulator of ``dtype``, so the program holds one
    client's weights and gradient at a time whatever k is."""
    lr = jnp.asarray(cfg["client_lr"], dtype)

    def train(params, batch, w):
        def step(wt, b):
            value, g = jax.value_and_grad(loss)(wt, b)
            return jax.tree.map(lambda a, d: a - lr * d, wt, g), value

        def slot(acc, xs):
            cb, wk = xs
            w_end, values = jax.lax.scan(step, params, cb)
            acc = jax.tree.map(
                lambda s, a, p: s + wk.astype(a.dtype) * (a - p),
                acc, w_end, params)
            return acc, values.mean()

        acc = jax.tree.map(jnp.zeros_like, params)
        delta, losses = jax.lax.scan(slot, acc, (batch, w))
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(d.astype(jnp.float32)))
                            for d in jax.tree.leaves(delta)))
        new = jax.tree.map(lambda a, d: a + cfg["server_lr"] * d,
                           params, delta)
        return new, losses.astype(jnp.float32).mean(), norm

    return jax.jit(train, donate_argnums=0)


def _run(cell, inputs, seed, rounds, dtype, sdt, dot):
    cfg, mod = cell.config, cell.module
    n, k = cell.n_clients, _budget(cell.traffic)
    steps, bsz = cfg["local_steps"], cfg["local_batch"]
    beta = float(cfg["rate_beta"])
    q = jnp.asarray(availability_marginals(cell.traffic, n))
    p = jnp.asarray(inputs.p, jnp.float32).astype(sdt)
    r = jnp.full((n,), np.float32(min(1.0, k / n)), jnp.float32).astype(sdt)
    key = jax.random.PRNGKey(seed)
    params = jax.tree.map(lambda a: a.astype(dtype),
                          jax.jit(lambda kk: mod.init_params(cfg, kk))(key))
    params0 = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    staged = (None if inputs.arrays is None else
              {name: jnp.asarray(a) for name, a in inputs.arrays.items()})
    counts = None if inputs.counts is None else np.asarray(inputs.counts)
    loss = lambda w, b: mod.reference_loss(cfg, w, b, dot)  # noqa: E731

    @jax.jit
    def draw(k_av, k_sel, r, q, p):
        up = jax.random.bernoulli(k_av, q)
        tie = jax.random.uniform(jax.random.fold_in(k_av, 1), (n,))
        wake = jnp.arange(n) == jnp.argmax(jnp.where(q >= q.max(), tie, -1.0))
        avail = jnp.where(up.any(), up, wake)
        rc = jnp.maximum(r, R_MIN)
        util = p * p / (rc * rc)
        tie = jax.random.uniform(k_sel, (n,)).astype(sdt)
        return avail, (util * (1.0 + 1e-6 * tie)).astype(jnp.float32)

    @jax.jit
    def rates(r, sel, p):
        r = (1.0 - beta) * r + beta * sel.astype(sdt)
        return r, p / jnp.maximum(r, R_MIN)

    @jax.jit
    def batch_of(k_batch, ids, data):
        rows = jnp.arange(k)[:, None, None]
        if data is None:
            block = mod.reference_block(cfg, ids)
            idx = jax.random.randint(
                k_batch, (k, steps, bsz), 0,
                jnp.full((k, 1, 1), cfg["samples_per_client"], jnp.int32))
            return {name: a[rows, idx] for name, a in block.items()}
        data, cnt = data
        idx = jax.random.randint(k_batch, (k, steps, bsz), 0,
                                 cnt[:, None, None])
        return {name: a[ids[:, None, None], idx] for name, a in data.items()}

    train = cohort_train(cfg, loss, dtype)

    out = {name: [] for name in ("sel", "k_t", "n_available", "loss",
                                 "delta_norm")}
    for _ in range(rounds):
        key, k_av, k_sel, _k_bud, k_batch = jax.random.split(key, 5)
        avail, score = draw(k_av, k_sel, r, q, p)
        avail, score = np.asarray(avail), np.asarray(score)
        k_eff = min(k, int(avail.sum()))
        order = np.argsort(-np.where(avail, score, _NEG), kind="stable")
        sel = np.zeros(n, bool)
        sel[order[:k_eff]] = True
        r, w_full = rates(r, jnp.asarray(sel), p)
        ids = np.flatnonzero(sel)
        valid = np.arange(k) < ids.size
        ids = np.concatenate([ids, np.full(k - ids.size, ids[0])]).astype(np.int32)
        w = np.asarray(w_full.astype(jnp.float32))[ids] * valid
        data = None if staged is None else (staged, jnp.asarray(counts[ids]))
        batch = batch_of(k_batch, jnp.asarray(ids), data)
        params, round_loss, norm = train(params, batch, jnp.asarray(w))
        out["sel"].append(sel)
        out["k_t"].append(k)
        out["n_available"].append(int(avail.sum()))
        out["loss"].append(float(round_loss))
        out["delta_norm"].append(float(norm))
    res = {name: np.asarray(v) for name, v in out.items()}
    res["params"] = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    res["params0"] = params0
    res["r"] = np.asarray(r.astype(jnp.float32))
    return res
