"""Client-sharded engine ⇔ single-device engine ⇔ host-loop parity.

The sharded engine (``sim/engine_sharded.py``) partitions the client
dimension over the ``clients`` axis of a ``(clients,)`` or
``(clients, model)`` mesh (the 2-D parity cells live in
``test_parity_matrix.py``).  Parity is required to be *exact*
for everything the selection dynamics depend on: for the same seed the
selection masks and r_k trajectories must be bit-identical across the three
engines, and losses must agree to float tolerance (the psum reduction order
in the delta aggregation is the only divergence).

Run under multiple devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI multi-device
job does); on a single device the mesh degenerates to one shard but
exercises the same shard_map program.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from conftest import assert_cell_parity, parity_spec, run_cell
from repro.core.selection import (_topk_mask, cohort_ids_from_mask,
                                  sharded_cohort_ids_from_mask,
                                  sharded_topk_mask)
from repro.launch.mesh import make_client_mesh
from repro.sim import run_scenario

ROUNDS = 12


def _run(algo, scenario, engine, mesh_shape=None, rounds=ROUNDS, **kw):
    if mesh_shape is not None:
        kw["mesh_shape"] = mesh_shape
    return run_cell(parity_spec(algo, scenario=scenario, rounds=rounds),
                    engine, **kw)


# ---------------------------------------------------------------------------
# Engine-level parity: sharded ⇔ device ⇔ host
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario,algo", [
    ("scarce", "f3ast"),
    ("scarce", "fedavg"),
    ("scarce", "fedavg_weighted"),
    ("scarce", "uniform"),
    ("scarce", "fedadam"),         # alias resolved identically per engine
    ("stepk", "f3ast"),            # time-varying K_t budget
    ("gilbert_elliott", "f3ast"),  # stateful (N,)-shaped availability state
    ("markov", "f3ast"),           # cluster-level (non-client-dim) state
])
def test_sharded_engine_matches_device_and_host(scenario, algo):
    host = _run(algo, scenario, "host")
    dev = _run(algo, scenario, "device")
    sh = _run(algo, scenario, "device", mesh_shape=(0,))   # all visible devices
    assert sh.final_metrics["engine"] == "sharded"
    # masks bit-identical everywhere; rate EMA bit-identical between the
    # two compiled engines, float-tolerance vs the host loop
    assert_cell_parity(host, dev)
    assert_cell_parity(dev, sh, rates_exact=True)
    np.testing.assert_allclose(sh.rates, host.rates, atol=1e-6)
    assert sh.rates.shape == (dev.sel_history.shape[1],)   # padding sliced
    assert sh.final_metrics["test_loss"] == pytest.approx(
        host.final_metrics["test_loss"], abs=1e-5)


def test_sharded_parity_independent_of_chunk_size():
    a = _run("f3ast", "scarce", "device", mesh_shape=(0,), chunk_size=12)
    b = _run("f3ast", "scarce", "device", mesh_shape=(0,), chunk_size=5)
    np.testing.assert_array_equal(a.sel_history, b.sel_history)
    assert a.final_metrics["test_loss"] == pytest.approx(
        b.final_metrics["test_loss"], rel=1e-5)


def test_sharded_rejects_sequential_fed_mode():
    from repro.sim.engine import build_engine
    with pytest.raises(ValueError, match="parallel"):
        build_engine("scarce", "f3ast", fed_mode="sequential", mesh=0)


def test_host_engine_rejects_mesh():
    # mesh= only applies to the device engine; silently dropping it would
    # let '--engine host --mesh 8' run unsharded without notice
    with pytest.raises(ValueError, match="host"):
        _run("f3ast", "scarce", "host", mesh_shape=(0,), rounds=2)


# ---------------------------------------------------------------------------
# Distributed primitives vs their single-device references
# ---------------------------------------------------------------------------

def _client_mesh():
    return make_client_mesh(axis_name="clients")


@pytest.mark.parametrize("method", ["allgather", "stream"])
def test_sharded_topk_mask_matches_topk_mask(method):
    mesh = _client_mesh()
    shards = mesh.shape["clients"]
    n = 24 * shards
    k_max = 7

    f = jax.jit(jax.shard_map(
        lambda s, a, k: sharded_topk_mask(s, a, k, "clients", k_max,
                                          method=method),
        mesh=mesh, in_specs=(P("clients"), P("clients"), P()),
        out_specs=P("clients"), check_vma=False))

    def check(scores, avail, k, label):
        want = np.asarray(_topk_mask(jnp.asarray(scores), jnp.asarray(avail),
                                     jnp.asarray(np.int32(k))))
        got = np.asarray(f(jnp.asarray(scores), jnp.asarray(avail),
                           jnp.asarray(np.int32(k))))
        np.testing.assert_array_equal(got, want, err_msg=str(label))

    rng = np.random.default_rng(0)
    for trial in range(20):
        # coarse integer-valued scores: plenty of exact ties to stress the
        # (score, index) tie-break equivalence
        scores = rng.integers(0, 5, n).astype(np.float32)
        avail = rng.random(n) < 0.4
        if not avail.any():
            avail[rng.integers(n)] = True
        k = rng.integers(1, k_max + 1)
        check(scores, avail, k, f"trial {trial}")

    # edge cases: zero budget, budget above |available|, nobody available
    scores = rng.integers(0, 3, n).astype(np.float32)
    some = rng.random(n) < 0.3
    sparse = np.zeros(n, bool)
    sparse[rng.choice(n, size=min(3, k_max - 1), replace=False)] = True
    check(scores, some, 0, "k=0")
    check(scores, sparse, k_max, "k > |available|")
    check(scores, np.zeros(n, bool), k_max, "all unavailable")


@pytest.mark.parametrize("method", ["allgather", "stream"])
def test_sharded_cohort_ids_matches_reference(method):
    mesh = _client_mesh()
    shards = mesh.shape["clients"]
    n = 16 * shards
    cohort = 6

    f = jax.jit(jax.shard_map(
        lambda m: sharded_cohort_ids_from_mask(m, cohort, "clients", n,
                                               method=method),
        mesh=mesh, in_specs=P("clients"), out_specs=(P(), P()),
        check_vma=False))

    rng = np.random.default_rng(1)
    for _ in range(20):
        mask = rng.random(n) < 0.15
        if not mask.any():
            mask[rng.integers(n)] = True
        want_ids, want_valid = cohort_ids_from_mask(jnp.asarray(mask), cohort)
        ids, valid = f(jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
        np.testing.assert_array_equal(np.asarray(valid),
                                      np.asarray(want_valid))


# ---------------------------------------------------------------------------
# cohort_ids_from_mask edge cases (single-device reference semantics)
# ---------------------------------------------------------------------------

def test_cohort_ids_underfull_mask_pads_with_first_selected():
    # fewer set bits than cohort_size: pad slots repeat the first selected
    # client and are flagged invalid
    mask = np.zeros(11, bool)
    mask[[3, 8]] = True
    ids, valid = cohort_ids_from_mask(jnp.asarray(mask), 5)
    np.testing.assert_array_equal(np.asarray(ids), [3, 8, 3, 3, 3])
    np.testing.assert_array_equal(np.asarray(valid),
                                  [True, True, False, False, False])


def test_cohort_ids_all_zero_mask_is_all_invalid():
    # an all-zero availability round: no valid slot, ids clamp to the last
    # client (never aggregated — every weight is masked by valid=False)
    n, k = 9, 4
    ids, valid = cohort_ids_from_mask(jnp.zeros(n, bool), k)
    assert not np.asarray(valid).any()
    np.testing.assert_array_equal(np.asarray(ids), [n - 1] * k)
    # sharded path agrees
    mesh = _client_mesh()
    shards = mesh.shape["clients"]
    n2 = 8 * shards
    f = jax.jit(jax.shard_map(
        lambda m: sharded_cohort_ids_from_mask(m, k, "clients", n2),
        mesh=mesh, in_specs=P("clients"), out_specs=(P(), P()),
        check_vma=False))
    ids2, valid2 = f(jnp.zeros(n2, bool))
    assert not np.asarray(valid2).any()
    np.testing.assert_array_equal(np.asarray(ids2), [n2 - 1] * k)


# ---------------------------------------------------------------------------
# On-demand cohort synthesis (SynthTask) vs staged arrays
# ---------------------------------------------------------------------------

def test_synth_cohort_batch_matches_staged_bitwise():
    # the cross-path anchor: synthesizing only the cohort block must equal
    # gathering from fully materialized (N, S, ...) arrays, bit for bit
    from repro.data import SynthTask, stage_synth_task, synth_cohort_batch
    from repro.data.pipeline import staged_cohort_batch
    task = SynthTask(n_clients=300, seed=7)
    staged = stage_synth_task(task)
    rng = np.random.default_rng(2)
    for trial in range(5):
        key = jax.random.PRNGKey(trial)
        ids = jnp.asarray(rng.integers(0, 300, 10), jnp.int32)
        want = staged_cohort_batch(staged, key, ids, 5, 20)
        got = synth_cohort_batch(task, key, ids, 5, 20)
        assert set(want) == set(got)
        for name in want:
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          np.asarray(want[name]),
                                          err_msg=f"{name} trial {trial}")


def test_stage_client_arrays_mesh_pads_to_shard_quantum():
    from repro.data import SynthTask, stage_synth_task
    from repro.data.pipeline import SHARD_PAD_QUANTUM
    mesh = _client_mesh()
    shards = mesh.shape["clients"]
    task = SynthTask(n_clients=300, seed=1)
    staged = stage_synth_task(task, mesh=mesh)
    n_pad = int(staged.counts.shape[0])
    quantum = shards * SHARD_PAD_QUANTUM
    assert n_pad % quantum == 0 and n_pad >= 300
    counts = np.asarray(staged.counts)
    assert (counts[:300] == task.samples_per_client).all()
    assert (counts[300:] == 1).all()            # padded clients: inert
    ref = stage_synth_task(task)                # unsharded layout
    for name, arr in staged.arrays.items():
        np.testing.assert_array_equal(
            np.asarray(arr)[:300], np.asarray(ref.arrays[name]),
            err_msg=name)
        assert not np.asarray(arr)[300:].any()  # zero padding rows


def _synth_engine(staged, n, mesh=None, topk_impl="stream"):
    import functools
    from repro.core.fedstep import make_fed_round
    from repro.core.strategies import make_strategy
    from repro.models import softmax_reg
    from repro.models.softmax_reg import SoftmaxRegConfig
    from repro.optim import make_optimizer
    from repro.sim.budgets import make_budget
    from repro.sim.engine import DeviceEngine
    from repro.sim.engine_sharded import ShardedEngine
    from repro.sim.processes import make_process
    k = 8
    cfg = SoftmaxRegConfig(dim=32, n_classes=10)
    loss = functools.partial(softmax_reg.loss_fn, cfg)
    opt = make_optimizer("sgd", lr=1.0)
    common = dict(avail_model=make_process("bernoulli", n, q=0.3),
                  budget=make_budget("constant", k=k),
                  strategy=make_strategy(
                      "f3ast", n, np.full(n, 1.0 / n, np.float32),
                      clients_per_round=k),
                  init_params=functools.partial(softmax_reg.init_params, cfg),
                  opt=opt, client_lr=0.05, local_steps=3, local_batch=16)
    if mesh is None:
        return DeviceEngine(staged=staged,
                            fed_round=make_fed_round(loss, opt), **common)
    return ShardedEngine(mesh=mesh, axis="clients", staged=staged,
                         n_clients=n, topk_impl=topk_impl,
                         fed_round=make_fed_round(loss, opt,
                                                  cohort_axis="clients",
                                                  cohort_slots=k), **common)


def test_synth_engines_match_staged_engine():
    # SynthTask engines (device + sharded, both top-k impls) vs the staged
    # device engine: masks/K_t bit-identical, losses to float tolerance
    # (fusing the synthesis into the scan reorders a few f32 ops)
    from repro.data import SynthTask, stage_synth_task
    from repro.sim.engine import _unpack_stream
    n, rounds = 200, 10
    task = SynthTask(n_clients=n, seed=3)
    mesh = _client_mesh()
    engines = {
        "staged": _synth_engine(stage_synth_task(task), n),
        "synth": _synth_engine(task, n),
        "sharded_stream": _synth_engine(task, n, mesh, "stream"),
        "sharded_allgather": _synth_engine(task, n, mesh, "allgather"),
    }
    outs = {}
    for name, engine in engines.items():
        carry = engine.init_carry(jax.random.PRNGKey(0))
        _, out = engine.chunk(carry, jnp.arange(rounds, dtype=jnp.int32))
        outs[name] = _unpack_stream(jax.tree.map(np.asarray, out), n)
    ref = outs["staged"]
    for name in ("synth", "sharded_stream", "sharded_allgather"):
        np.testing.assert_array_equal(ref.sel_mask, outs[name].sel_mask,
                                      err_msg=name)
        np.testing.assert_array_equal(ref.completed, outs[name].completed,
                                      err_msg=name)
        np.testing.assert_array_equal(ref.k_t, outs[name].k_t, err_msg=name)
        np.testing.assert_allclose(ref.train_loss, outs[name].train_loss,
                                   atol=1e-5, err_msg=name)
    # scale accounting: on-demand synthesis keeps nothing resident
    assert engines["staged"].n_staged_bytes > 0
    assert engines["synth"].n_staged_bytes == 0
    assert engines["sharded_stream"].n_staged_bytes == 0
    if mesh.shape["clients"] > 1:
        assert engines["sharded_stream"].selection_comm_bytes_per_round > 0
        assert (engines["sharded_stream"].selection_comm_bytes_per_round
                < engines["sharded_allgather"].selection_comm_bytes_per_round)


def test_topk_impl_engine_parity():
    # RunSpec.topk_impl: streaming and all_gather reductions must produce
    # the same trajectory, bit for bit (rates included)
    stream = _run("f3ast", "scarce", "device", mesh_shape=(0,), topk_impl="stream")
    allg = _run("f3ast", "scarce", "device", mesh_shape=(0,), topk_impl="allgather")
    assert_cell_parity(stream, allg, rates_exact=True)


def test_spec_rejects_unknown_topk_impl():
    with pytest.raises(ValueError, match="topk_impl"):
        parity_spec("f3ast", topk_impl="bogus").resolved()


def test_final_metrics_surface_scale_accounting():
    res = _run("f3ast", "scarce", "device", mesh_shape=(0,), rounds=4)
    assert res.final_metrics["n_staged_bytes"] > 0       # staged scenario data
    assert res.final_metrics["selection_comm_bytes_per_round"] >= 0
    host = _run("f3ast", "scarce", "host", rounds=4)
    assert host.final_metrics["n_staged_bytes"] == 0     # numpy-resident


# ---------------------------------------------------------------------------
# Engine reporting: metrics name the engine; host-only fallback warns
# ---------------------------------------------------------------------------

def test_final_metrics_surface_the_engine():
    assert _run("f3ast", "scarce", "host",
                rounds=4).final_metrics["engine"] == "host"
    assert _run("f3ast", "scarce", "device",
                rounds=4).final_metrics["engine"] == "device"
    assert _run("f3ast", "scarce", "device", mesh_shape=(0,),
                rounds=4).final_metrics["engine"] == "sharded"


def test_poc_fallback_warns_and_reports_host_engine():
    with pytest.warns(UserWarning, match="poc.*host"):
        res = _run("poc", "scarce", "device", rounds=3)
    assert res.final_metrics["engine"] == "host"
    assert "per-client losses" in res.final_metrics["engine_fallback"]
    assert np.isfinite(res.final_metrics["test_loss"])


# ---------------------------------------------------------------------------
# Real multi-device coverage even when the parent runs on one device
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() >= 2,
                    reason="already multi-device; in-process tests cover it")
def test_sharded_parity_under_forced_8_devices_subprocess():
    code = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # the forced-device flag is CPU-only
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
assert jax.device_count() == 8, jax.device_count()
from repro.sim import run_scenario
silent = lambda *a, **k: None
dev = run_scenario("scarce", "f3ast", rounds=8, seed=0, eval_every=8,
                   engine="device", log_fn=silent)
sh = run_scenario("scarce", "f3ast", rounds=8, seed=0, eval_every=8,
                  engine="device", mesh_shape=(0,), log_fn=silent)
assert np.array_equal(dev.sel_history, sh.sel_history)
assert np.array_equal(dev.rates, sh.rates)
assert abs(dev.final_metrics["test_loss"] - sh.final_metrics["test_loss"]) < 1e-5
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
