"""The per-layer reduction (``bench/lib/scopes.py``): self time by scope
and idle time by innermost host span on hand-made events, scope
attribution through a real CPU profiler trace of a small chunk, and a
trace recorded on the chip; the per-layer metrics' readers on it."""
import copy
import importlib
import itertools
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.lib import scopes, trace

DATA = Path(__file__).resolve().parent / "data"


def test_scope_of_keeps_the_layer_names_and_the_mesh_axis():
    assert scopes.scope_of(
        "jit(chunk)/while/body/closed_call/select/topk/jit(argsort)/sort"
    ) == "select/topk"
    assert scopes.scope_of("jit(chunk)/shard_map/while/body/closed_call/"
                           "aggregate/collective/clients/psum") == \
        "aggregate/collective/clients"
    assert scopes.scope_of("jit(chunk)/while/body/add") == scopes.UNSCOPED
    assert scopes.scope_of(None) == scopes.UNSCOPED


def test_scope_of_keeps_a_configurations_scopes_beneath_local_sgd():
    path = ("jit(chunk)/while/body/closed_call/local_sgd/while/body/"
            "mamba/ssd/dot_general")
    assert scopes.scope_of(path, ("mamba", "ssd")) == "local_sgd/mamba/ssd"
    assert scopes.scope_of(path, ("mamba",)) == "local_sgd/mamba"
    # a name no configuration lists folds into its round layer
    assert scopes.scope_of(path) == "local_sgd"
    assert scopes.scope_of(path, ("attention",)) == "local_sgd"
    # a listed name outside local_sgd is no layer
    assert scopes.scope_of("jit(chunk)/while/body/mamba/add",
                           ("mamba",)) == scopes.UNSCOPED


def test_a_configurations_scope_becomes_a_layer_time_and_a_roofline(
        tmp_path):
    """A CPU trace of a program with scopes inside ``local_sgd``: the one
    that ``model`` lists is read as a layer of its own, by the helpers a
    reader calls, and one it does not list falls to ``local_sgd``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("local_sgd"):
            with jax.named_scope("mamba"):
                x = jnp.sin(x) @ x
            with jax.named_scope("attention"):
                x = jnp.tanh(x) @ x
        return x

    x = jnp.ones((256, 256))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chunk_dispatch", rounds=10):
                y = step(x)
            y.block_until_ready()
    jax.profiler.stop_trace()
    text = step.lower(x).compile().as_text()
    events = scopes.load(str(tmp_path), text, ("mamba",))
    found = set(scopes.self_ns(events))
    assert {"local_sgd/mamba", "local_sgd"} <= found, found
    ms = scopes.layer_ms_per_round(events, ("local_sgd/mamba",))
    assert ms is not None and ms > 0
    assert scopes.layer_ms_per_round(events, ("local_sgd",)) > ms
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    flops = 2 * 256**3 * 3 / 30          # one round's share of the dots
    pct = scopes.roofline_pct(events, ("local_sgd/mamba",), flops, 0.0,
                              peak)
    assert pct == pytest.approx(100 * flops / 1e12 / (1e-3 * ms))
    assert scopes.layer_ms_per_round(events, ("local_sgd/ssd",)) is None
    assert scopes.roofline_pct(events, ("local_sgd/ssd",), flops, 0.0,
                               peak) is None


def test_hlo_op_names_maps_instructions_to_op_name():
    text = (
        'HloModule jit_chunk, entry_computation_layout={()->()}\n'
        '  %sort.45 = (f32[8]{0}, s32[8]{0}) sort(%a, %b), dimensions={0}, '
        'metadata={op_name="jit(chunk)/while/body/closed_call/select/topk/'
        'jit(argsort)/sort" stack_frame_id=7}\n'
        '  ROOT %fusion.3 = f32[8]{0} fusion(%c), kind=kLoop, '
        'metadata={op_name="jit(chunk)/while/body/closed_call/avail/lt"}\n')
    module, names = scopes.hlo_op_names(text)
    assert module == "jit_chunk"
    assert {k: scopes.scope_of(v) for k, v in names.items()} == {
        "sort.45": "select/topk", "fusion.3": "avail"}


def test_self_time_takes_nested_ops_out_of_their_parent():
    # a loop (0-100) enclosing its body ops, an op after it, one past the
    # window's end
    ops = [("while", 0, 100), ("fusion", 10, 30), ("sort", 30, 70),
           ("inner", 40, 50), ("copy", 110, 120), ("late", 190, 260)]
    sc = ["unscoped", "avail", "select/topk", "select/topk", "cohort",
          "stream"]
    out = scopes.self_time(ops, sc, 0, 200)
    assert out == {"unscoped": 40, "avail": 20, "select/topk": 40,
                   "cohort": 10, "stream": 10}
    busy = sum(e - s for s, e in trace.merge([op[1:] for op in ops], 0, 200))
    assert sum(out.values()) == busy


def test_self_time_counts_an_op_past_its_parent_once():
    ops = [("a", 0, 100), ("b", 10, 20), ("c", 15, 110)]
    out = scopes.self_time(ops, ["x", "y", "z"], 0, 200)
    assert sum(out.values()) == 110
    assert out == {"x": 10, "y": 5, "z": 95}


def test_idle_by_span_goes_to_the_innermost_span():
    ops = [("op", 10, 20), ("op", 60, 70)]
    spans = [("window", 0, 100, {}), ("dispatch", 0, 15, {}),
             ("chunk_dispatch", 2, 8, {"rounds": 10}),
             ("sync", 20, 55, {}), ("stream_decode", 30, 50, {}),
             ("wait", 70, 80, {})]
    out = scopes.idle_by_span(ops, spans, 0, 100)
    assert out == {"chunk_dispatch": 6, "dispatch": 4, "sync": 15,
                   "stream_decode": 20, "wait": 10, "none": 25}
    assert sum(out.values()) == 80


def test_summarize_by_hand():
    events = {
        "devices": {"/device:TPU:0": [("while", 100, 1100),
                                      ("sort", 200, 700),
                                      ("fusion", 700, 900)]},
        "scopes": {"/device:TPU:0": ["unscoped", "select/topk", "cohort"]},
        "spans": [("window", 0, 2000, {}),
                  ("chunk_dispatch", 50, 90, {"rounds": 10}),
                  ("stream_decode", 1200, 1700, {"clients": 4, "bytes": 80}),
                  ("chunk_dispatch", 2100, 2190, {"rounds": 10})],
    }
    out = scopes.summarize(events)
    assert out["rounds"] == 10
    assert out["busy_ns"] == 1000
    assert out["self_ns"] == {"select/topk": 500, "unscoped": 300,
                              "cohort": 200}
    assert out["idle_ns"] == {"none": 460, "stream_decode": 500,
                              "chunk_dispatch": 40}
    layers = out["layers"]
    assert layers["select_ms_per_round"] == pytest.approx(5e-5)
    assert layers["cohort_ms_per_round"] == pytest.approx(2e-5)
    assert layers["decode_ms_per_round"] == pytest.approx(5e-5)
    # scopes the trace lacks read None, as on a program without scopes
    assert layers["avail_ms_per_round"] is None
    assert layers["local_sgd_ms_per_round"] is None


def test_scopes_through_a_cpu_trace_of_a_small_chunk():
    """The layer tool on the CPU: every op of the chunk program takes the
    scope of its ``op_name``, the scopes sum to the busy time, and the
    rounds come from the ``chunk_dispatch`` counters."""
    from bench.layers import measure
    from bench.lib.build import build_engine, make_inputs
    from bench.lib.spec import load_cell

    cell = load_cell("synthetic_softmax.paper")
    engine = build_engine(cell, make_inputs(cell))
    events, rates = measure(engine, seed=5, size=cell.chunk_size, chunks=2)
    assert rates["untraced"] > 0 and rates["traced"] > 0
    out = scopes.summarize(events)
    assert out["rounds"] == 2 * cell.chunk_size
    assert sum(out["self_ns"].values()) == pytest.approx(out["busy_ns"])
    for name in ("avail", "budget", "select", "select/topk", "cohort",
                 "local_sgd", "aggregate", "server_update", "stream"):
        assert out["self_ns"].get(name, 0) > 0, (name, out["self_ns"])
    for name, value in out["layers"].items():
        assert value is not None and value > 0, name
    assert {"chunk_dispatch", "stream_decode", "dispatch", "wait",
            "sync"} <= {name for name, *_ in events["spans"]}
    stripped = json.loads(json.dumps(scopes.strip(events)))
    assert scopes.summarize(stripped)["self_ns"] == pytest.approx(
        out["self_ns"])


RECORDED = DATA / "synthetic_softmax_paper_two_chunks.json"


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_chip_trace_scopes_sum_to_busy(recorded):
    """A ``bench/layers.py --chunks 2`` window of ``synthetic_softmax.paper``
    on a TPU v5e (its op events carry no op_name: the scopes came from the
    compiled text): the scopes and ``unscoped`` cover the busy time."""
    (dev,) = recorded["devices"]
    assert dev.startswith("/device:TPU:")
    out = scopes.summarize(recorded)
    assert out["rounds"] == 20
    assert sum(out["self_ns"].values()) == pytest.approx(out["busy_ns"],
                                                         rel=0.02)
    summary = trace.reduce(recorded)
    assert out["busy_ns"] == pytest.approx(1e9 * summary["busy_s"])
    for name in ("avail", "select", "select/topk", "cohort", "local_sgd",
                 "aggregate"):
        assert out["self_ns"].get(name, 0) > 0, (name, out["self_ns"])


def test_recorded_chip_trace_is_one_clock_up_to_an_offset(recorded):
    """Host and device events of the chip's trace keep one clock up to a
    constant offset, and the offset is not zero: each chunk's ops start
    ~1.66 ms (device clock) before its ``chunk_dispatch`` span returns (host
    clock), the same for both chunks within 50 us, and one shift puts every
    op of chunk i between the start of its ``chunk_dispatch`` and the end
    of its ``wait``.  Idle time attributed to host spans is off by that
    shift (PERF.md §7)."""
    slack = 50e3
    spans = recorded["spans"]
    dispatch = sorted((s, e) for name, s, e, _ in spans
                      if name == "chunk_dispatch")
    waits = sorted(e for name, _, e, _ in spans if name == "wait")
    (ops,) = recorded["devices"].values()
    ops = sorted((s, e) for _, s, e in ops)
    # the device runs the two chunks apart: split at the longest gap
    ends = list(itertools.accumulate((e for _, e in ops), max))
    cut = max(range(1, len(ops)), key=lambda i: ops[i][0] - ends[i - 1])
    chunks = [ops[:cut], ops[cut:]]
    assert len(dispatch) == len(waits) == len(chunks) == 2
    lead = [d[1] - c[0][0] for d, c in zip(dispatch, chunks)]
    assert abs(lead[0] - lead[1]) < slack, lead
    lo = max(d[0] - c[0][0] for d, c in zip(dispatch, chunks))
    hi = min(w - max(e for _, e in c) for w, c in zip(waits, chunks))
    assert slack < lo <= hi, (lo, hi)


# Each per-layer metric's reader on the recorded chip trace, in ms per
# round: the values ``scopes.summarize`` gave for it when recorded.
RECORDED_LAYERS = {
    "decode_ms_per_round": 0.00954405,
    "avail_ms_per_round": 0.00227085,
    "select_ms_per_round": 0.00652835,
    "cohort_ms_per_round": 0.03093005,
    "local_sgd_ms_per_round": 0.01963535,
    "aggregate_ms_per_round": 0.00123025,
}


def reader(name):
    return importlib.import_module(f"bench.metrics.{name}").read


@pytest.mark.parametrize("name", sorted(RECORDED_LAYERS))
def test_reader_reads_the_recorded_trace(recorded, name):
    events = copy.deepcopy(recorded)
    value = reader(name)(SimpleNamespace(events=events))
    assert value == pytest.approx(RECORDED_LAYERS[name], rel=1e-9)
    assert scopes.summarize(events)["layers"][name] == value


@pytest.mark.parametrize("name", sorted(RECORDED_LAYERS))
def test_reader_finds_nothing_without_scopes(name):
    events = {"devices": {"/device:TPU:0": [("while", 10, 90),
                                            ("fusion", 20, 40)]},
              "scopes": {"/device:TPU:0": [scopes.UNSCOPED] * 2},
              "spans": [("window", 0, 100, {}),
                        ("chunk_dispatch", 5, 8, {"rounds": 10}),
                        ("sync", 90, 99, {})]}
    assert reader(name)(SimpleNamespace(events=events)) is None
    assert reader(name)(SimpleNamespace(events=None)) is None


def test_traced_run_reports_the_layer_metrics():
    """A ``--trace 1`` run on the CPU, the look for a chip skipped: the
    harness fetches the chunk's compiled text after the window and hands
    the scoped events to the readers.  ``step_mfu_pct`` is left out, as
    the CPU has no published peak."""
    import dataclasses
    import time

    from bench.lib.harness import run_cell
    from bench.lib.spec import load_cell

    cell = load_cell("synthetic_softmax.paper")
    cell = dataclasses.replace(cell, per_layer=tuple(
        m for m in cell.per_layer if m["name"] != "step_mfu_pct"))
    result = run_cell(cell, 2**31 + 23, 0.5, True,
                      t_start=time.perf_counter())
    assert result["correct"], result["checks"]
    got = result["metrics"]
    assert set(got) == {m["name"] for m in cell.per_layer}
    for name in RECORDED_LAYERS:
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms", name
    assert result["device"]["busy_s"] > 0
