"""The comparison that decides ``correct``, on the CPU at sizes a test run
holds: a sound run passes, and so does the stand-in for the chip's
one-pass bfloat16 products; the control (the plain reference in
bfloat16, put in the system's place) and each fault planted under the
timed path fail.  The harness's look for a chip is skipped; the rest of a run is
driven as on the chip, with each cell's committed limits."""
import dataclasses
import time

import jax.numpy as jnp
import pytest

from bench.calibrate import as_program
from bench.lib import compare, faults
from bench.lib.build import make_inputs
from bench.lib.harness import run_cell
from bench.lib.reference import run_reference
from bench.lib.spec import load_cell

SMALL = {
    "synthetic_softmax.pop1m": {"traffic": {"n_clients": 4000}},
    "shakespeare_lstm.paper": {"config": {"hidden": 32, "seq_len": 24}},
    "synthetic_softmax.paper": {},
}


def small_cell(workload):
    cell = load_cell(workload)
    over = SMALL[workload]
    return dataclasses.replace(
        cell, config={**cell.config, **over.get("config", {})},
        traffic={**cell.traffic, **over.get("traffic", {})})


def run(cell, **plant):
    return run_cell(cell, 2**31 + 11, 0.2, False, t_start=time.perf_counter(),
                    **plant)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload):
    result = run(small_cell(workload))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.ENGINE_FAULTS))
@pytest.mark.parametrize("workload", sorted(SMALL))
def test_engine_fault_is_caught(workload, fault):
    result = run(small_cell(workload), wrap_engine=faults.ENGINE_FAULTS[fault])
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_half_batch_is_caught(workload):
    cell = small_cell(workload)
    loss = faults.half_batch(cell.module.program_loss(cell.config))
    result = run(cell, loss=loss)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_is_not_correct(workload):
    cell = small_cell(workload)
    inputs = make_inputs(cell)
    ref = run_reference(cell, inputs, 7, cell.chunk_size)
    ctrl = run_reference(cell, inputs, 7, cell.chunk_size, dtype=jnp.bfloat16)
    values = compare.numbers(as_program(ctrl), ref)
    values["compiles_in_window"] = 0
    correct, checks = compare.judge(values, cell.limits)
    assert not correct, checks


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_stand_in_is_correct(workload):
    cell = small_cell(workload)
    inputs = make_inputs(cell)
    ref = run_reference(cell, inputs, 7, cell.chunk_size)
    stand_in = run_reference(cell, inputs, 7, cell.chunk_size, one_pass=True)
    values = compare.numbers(as_program(stand_in), ref)
    values["compiles_in_window"] = 0
    correct, checks = compare.judge(values, cell.limits)
    assert correct, checks
    assert values["loss_gap"] > 0, checks
