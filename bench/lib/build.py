"""The one general generator: a cell's configuration and traffic mix
become the system's round engine, plus the inputs the benchmark made
for it.

The system under test is ``repro.sim.engine.DeviceEngine`` assembled from
the repository's own registries: the availability process and K_t budget
the mix names (``sim/processes.py``, ``sim/budgets.py``), the selection
strategy (``core/strategies.py``), the federated round
(``core/fedstep.make_fed_round``) over the configuration's model loss, the
server optimizer, and the client data: staged on the device
(``data/pipeline.stage_client_arrays``) or synthesized inside the round
(``data.synthetic.SynthTask``).  The data and the weights are made here,
from the configuration's data seed and the run's seed, so that the plain
reference can be handed the same inputs and nothing the system made.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np


@dataclasses.dataclass
class Inputs:
    """What the benchmark made and handed to the system and the reference."""
    p: np.ndarray                 # (N,) f32 client weights p_k
    arrays: dict | None           # staged client data, or None (on demand)
    counts: np.ndarray | None     # (N,) i32 samples per client


def make_inputs(cell) -> Inputs:
    n = cell.n_clients
    data = cell.module.make_data(cell.config, n, cell.traffic["data"])
    if data is None:
        return Inputs(p=np.full(n, 1.0 / n, np.float32), arrays=None,
                      counts=None)
    arrays, counts = data
    sizes = counts.astype(np.float64)
    return Inputs(p=(sizes / sizes.sum()).astype(np.float32), arrays=arrays,
                  counts=counts)


def build_engine(cell, inputs: Inputs, *, loss=None):
    """The system's compiled round engine for ``cell``.  ``loss`` swaps the
    model loss the round trains (the fault checks plant one)."""
    from repro.core.fedstep import make_fed_round
    from repro.core.strategies import make_strategy
    from repro.data.pipeline import stage_client_arrays
    from repro.optim import make_optimizer
    from repro.sim.budgets import make_budget
    from repro.sim.engine import DeviceEngine
    from repro.sim.processes import make_process

    cfg, tr, mod = cell.config, cell.traffic, cell.module
    n = cell.n_clients
    if inputs.arrays is None:
        staged = mod.program_synth(cfg, n)
    else:
        staged = stage_client_arrays(inputs.arrays, inputs.counts)
    opt = make_optimizer(cfg["server_opt"], lr=cfg["server_lr"])
    return DeviceEngine(
        avail_model=make_process(tr["availability"], n, p=inputs.p,
                                 **tr["availability_kwargs"]),
        budget=make_budget(tr["budget"], **tr["budget_kwargs"]),
        strategy=make_strategy(tr["strategy"], n, inputs.p,
                               beta=cfg["rate_beta"],
                               clients_per_round=cell.k),
        staged=staged,
        fed_round=make_fed_round(loss or mod.program_loss(cfg), opt),
        init_params=jax.jit(functools.partial(mod.init_params, cfg)),
        opt=opt, client_lr=cfg["client_lr"],
        local_steps=cfg["local_steps"], local_batch=cfg["local_batch"])
