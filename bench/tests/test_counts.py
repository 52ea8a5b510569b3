"""Cross-check of the configurations' per-round FLOP counts against the
program's trip-count-aware HLO totals (``launch/hlo_costs.py``) on the
CPU, at a small size: the counts are lower bounds of the same matrix
products, so they sit just under the compiler's totals."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench.lib.spec import load_cell

CASES = {
    "synthetic_softmax.paper": {},
    "shakespeare_lstm.paper": {"hidden": 32, "seq_len": 12},
}


@pytest.mark.parametrize("workload", sorted(CASES))
def test_round_flops_against_hlo(workload):
    from repro.core.fedstep import make_fed_round
    from repro.launch.hlo_costs import analyze
    from repro.optim import make_optimizer

    cell = load_cell(workload)
    cfg = {**cell.config, **CASES[workload]}
    cell = dataclasses.replace(cell, config=cfg)
    mod, k = cell.module, 3
    e, b = cfg["local_steps"], cfg["local_batch"]
    params = jax.eval_shape(lambda key: mod.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    if "seq_len" in cfg:
        batch = {"tokens": jax.ShapeDtypeStruct((k, e, b, cfg["seq_len"]),
                                                jnp.int32)}
    else:
        batch = {"x": jax.ShapeDtypeStruct((k, e, b, cfg["dim"]), jnp.float32),
                 "y": jax.ShapeDtypeStruct((k, e, b), jnp.int32)}
    opt = make_optimizer("sgd", lr=1.0)
    fed_round = make_fed_round(mod.program_loss(cfg), opt)
    hlo = jax.jit(fed_round).lower(
        params, jax.eval_shape(opt.init, params), batch,
        jax.ShapeDtypeStruct((k,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    compiled = analyze(hlo)["flops"]
    counted, _ = mod.round_counts(cfg, 100, k)
    assert 0.9 * compiled <= counted <= compiled * 1.001, (counted, compiled)
