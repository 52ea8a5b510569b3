"""The round's layers in a profiler trace (``repro.core.spans``).

* The compiled chunk of every engine names each round layer in its ops'
  ``op_name`` metadata, and the sorts of the round fall under the top-k
  cut or the cohort gather.
* The run loops mark each chunk boundary with host spans whose counters
  (rounds, bytes) arrive as the trace event's stats.

That the scopes leave the computation alone is the engine parity suite's
job (``test_engine.py::test_device_engine_matches_host_runner``: masks,
rates and losses bitwise equal to the host loop).
"""
import collections
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import silent
from repro.core.spans import ROUND_SCOPES, scope
from repro.sim import RunSpec, run_scenario
from repro.sim.engine import build_engine

_OP = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = (.*?)metadata=\{[^}]*?"
                 r"op_name=\"([^\"]*)\"", re.M)


def chunk_ops(engine, rounds=3):
    """(instruction text, op_name) of each op of the compiled chunk."""
    carry = engine.init_carry(jax.random.PRNGKey(0))
    ts = jnp.arange(rounds, dtype=jnp.int32)
    text = jax.jit(engine.chunk).lower(carry, ts).compile().as_text()
    return _OP.findall(text)


def layers_of(op_name):
    """The layer names in an op_name path, outermost first."""
    return [p for p in op_name.split("/") if p in ROUND_SCOPES]


def check_chunk(engine, want):
    ops = chunk_ops(engine)
    seen = collections.Counter(name for _, path in ops
                               for name in layers_of(path))
    missing = [name for name in want if not seen[name]]
    assert not missing, (missing, seen)
    sorts = [layers_of(path) for instr, path in ops if " sort(" in instr]
    assert sorts
    for layers in sorts:
        assert layers[:2] == ["select", "topk"] or layers[:1] == ["cohort"], \
            layers
    assert any(layers[:2] == ["select", "topk"] for layers in sorts)
    return ops


@pytest.mark.parametrize("algo", ["f3ast", "fedavg", "uniform"])
def test_device_chunk_names_every_round_layer(algo):
    engine, _ = build_engine("scarce", algo, seed=0, completion="bernoulli",
                             completion_kwargs={"q": 0.6})
    ops = check_chunk(engine, [s for s in ROUND_SCOPES if s != "collective"])
    # the completion draw nests where selection calls it
    assert any(layers_of(p)[:2] == ["select", "complete"] for _, p in ops)


@pytest.mark.parametrize("fed_mode", ["parallel", "sequential"])
def test_fed_round_scopes_in_both_modes(fed_mode):
    engine, _ = build_engine("scarce", "f3ast", seed=0, fed_mode=fed_mode)
    check_chunk(engine, ["avail", "budget", "select", "topk", "cohort",
                         "stream", "local_sgd", "aggregate",
                         "server_update"])


def test_scope_refuses_a_name_outside_round_scopes():
    with pytest.raises(ValueError, match="not a round scope"):
        scope("selection")


_SHARDED = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
assert jax.device_count() == 4, jax.device_count()
from repro.sim.engine import build_engine
from test_spans import check_chunk, layers_of
engine, _ = build_engine("scarce", "f3ast", seed=0, mesh=(4,),
                         completion="bernoulli",
                         completion_kwargs={"q": 0.6})
assert type(engine).__name__ == "ShardedEngine"
ops = check_chunk(engine, ["avail", "budget", "select", "topk", "complete",
                           "cohort", "stream", "local_sgd", "aggregate",
                           "server_update"])
collectives = {p.split("collective/")[1].split("/")[0]
               for _, p in ops if "/collective/" in p}
assert collectives == {"clients"}, collectives
under = {tuple(layers_of(p)[:1]) for _, p in ops if "/collective/" in p}
assert ("aggregate",) in under and ("select",) in under, under
print("OK")
"""


def test_sharded_chunk_names_its_collectives():
    """On four CPU devices the sharded chunk names every layer and puts
    each collective under ``collective/clients``."""
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [here, os.path.join(here, "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# Host spans at the chunk boundary
# ---------------------------------------------------------------------------

HOST_SPANS = ("chunk_dispatch", "stream_pull", "stream_decode", "eval",
              "metrics_write", "checkpoint")


def host_spans(trace_dir):
    """(name, start, stats) of the run loops' spans, in order."""
    from jax.profiler import ProfileData

    paths = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, dict(list(ev.stats)))
                    for ev in line.events if ev.name in HOST_SPANS]
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("engine", ["device", "sharded", "buffered"])
def test_run_loop_spans_once_per_chunk(engine, tmp_path):
    # 9 rounds in chunks of 2: five chunks, evaluation in the chunks that
    # hold round 0, 4 or 8 (the last)
    spec = RunSpec(scenario="scarce", strategy="f3ast", rounds=9, seed=0,
                   eval_every=4, chunk_size=2, engine="device",
                   metrics_path=str(tmp_path / "m.jsonl"),
                   ckpt_dir=str(tmp_path / "ckpt"),
                   mesh_shape=(0,) if engine == "sharded" else None,
                   aggregation="buffered" if engine == "buffered" else "sync")
    run_scenario(spec, log_fn=silent)    # compiled before the trace
    with jax.profiler.trace(str(tmp_path / "trace")):
        res = run_scenario(spec, log_fn=silent)
    spans = host_spans(str(tmp_path / "trace"))
    by = collections.defaultdict(list)
    for name, _, stats in spans:
        by[name].append(stats)
    chunk_rounds = [2, 2, 2, 2, 1]
    assert [s["rounds"] for s in by["chunk_dispatch"]] == chunk_rounds
    assert len(by["eval"]) == 3
    assert len(by["metrics_write"]) == len(by["checkpoint"]) == 5
    n = res.sel_history.shape[1]
    if engine == "buffered":
        # the buffered stream is not packed: nothing to decode
        assert by["stream_decode"] == []
        assert len(by["stream_pull"]) == 5
        return
    words = -(-n // 32)
    # two packed masks, then k_t, n_available, train_loss, delta_norm
    assert [s["bytes"] for s in by["stream_pull"]] == [
        c * (2 * 4 * words + 4 * 4) for c in chunk_rounds]
    assert [(s["clients"], s["bytes"]) for s in by["stream_decode"]] == [
        (n, 2 * c * n) for c in chunk_rounds]
    # each chunk: dispatch, then pull, then decode
    order = [name for name, _, _ in spans
             if name in ("chunk_dispatch", "stream_pull", "stream_decode")]
    assert order == ["chunk_dispatch", "stream_pull", "stream_decode"] * 5
    assert np.isfinite(res.final_metrics["test_loss"])
