"""The plain reference trains the cohort one slot at a time: its compiled
temporaries do not grow with the cohort, and it gives what the former
form, every slot's model at once under ``vmap``, gave."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import reference
from bench.lib.build import make_inputs
from bench.lib.spec import load_cell

SMALL_LSTM = {"hidden": 32, "seq_len": 24}


def small_cell(workload):
    cell = load_cell(workload)
    if cell.config["name"] == "shakespeare_lstm":
        cell = dataclasses.replace(cell, config={**cell.config, **SMALL_LSTM})
    return cell


def vmap_train(cfg, loss, dtype):
    """The former form, kept here only as the oracle: every slot's weights
    and gradient at once, Delta one ``tensordot`` over the slots."""
    lr = jnp.asarray(cfg["client_lr"], dtype)

    def train(params, batch, w):
        def client(cb):
            def step(wt, b):
                value, g = jax.value_and_grad(loss)(wt, b)
                return jax.tree.map(lambda a, d: a - lr * d, wt, g), value
            w_end, values = jax.lax.scan(step, params, cb)
            return jax.tree.map(jnp.subtract, w_end, params), values.mean()

        deltas, losses = jax.vmap(client)(batch)
        delta = jax.tree.map(
            lambda d: jnp.tensordot(w.astype(d.dtype), d, axes=1), deltas)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(d.astype(jnp.float32)))
                            for d in jax.tree.leaves(delta)))
        new = jax.tree.map(lambda a, d: a + cfg["server_lr"] * d,
                           params, delta)
        return new, losses.astype(jnp.float32).mean(), norm

    return jax.jit(train)


def temp_bytes(make, cell, k):
    """``memory_analysis().temp_size_in_bytes`` of the compiled round
    step at cohort size ``k``, the bytes of its batch and of the model."""
    cfg, mod = cell.config, cell.module
    params = jax.eval_shape(lambda key: mod.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (k, cfg["local_steps"], cfg["local_batch"], cfg["seq_len"]),
        jnp.int32)}
    w = jax.ShapeDtypeStruct((k,), jnp.float32)
    loss = lambda p, b: mod.reference_loss(cfg, p, b)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        compiled = make(cfg, loss, jnp.float32).lower(params, batch,
                                                      w).compile()
    model = sum(a.size * 4 for a in jax.tree.leaves(params))
    return (compiled.memory_analysis().temp_size_in_bytes,
            batch["tokens"].size * 4, model)


def test_temporaries_do_not_grow_with_the_cohort():
    cell = small_cell("shakespeare_lstm.paper")
    t2, _, model = temp_bytes(reference.cohort_train, cell, 2)
    t8, batch8, _ = temp_bytes(reference.cohort_train, cell, 8)
    assert abs(t8 - t2) <= batch8, (t2, t8, batch8)
    # the oracle's temporaries hold a model copy or more per slot
    v2, _, _ = temp_bytes(vmap_train, cell, 2)
    v8, _, _ = temp_bytes(vmap_train, cell, 8)
    assert v8 - v2 >= 6 * model, (v2, v8, model)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("workload", ["synthetic_softmax.paper",
                                      "shakespeare_lstm.paper"])
def test_slot_by_slot_equals_the_vmap_form(workload, monkeypatch):
    cell = small_cell(workload)
    inputs = make_inputs(cell)
    new = reference.run_reference(cell, inputs, 2**31 + 5, 2)
    monkeypatch.setattr(reference, "cohort_train", vmap_train)
    old = reference.run_reference(cell, inputs, 2**31 + 5, 2)
    assert (new["sel"] == old["sel"]).all()
    for name in ("loss", "delta_norm"):
        assert rel(new[name], old[name]) <= 1e-6, name
    for a, b in zip(jax.tree.leaves(new["params"]),
                    jax.tree.leaves(old["params"])):
        assert rel(a, b) <= 1e-6
