"""The reduction from trace events to busy time, idle share, top device
operations and idle gaps by host span, on hand-made events; and the
reading of a trace the profiler wrote."""
import pytest

from bench.lib import scopes, trace


def test_merge_clips_and_joins():
    assert trace.merge([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 10) == [
        [1, 4], [5, 8], [9, 10]]


def test_reduce_by_hand():
    events = {
        "devices": {"/device:TPU:0": [("fusion", 10, 40), ("sort", 30, 60),
                                      ("fusion", 80, 90)]},
        "host_spans": [("window", 0, 100), ("dispatch", 0, 10),
                       ("sync", 60, 95)],
    }
    out = trace.reduce(events)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(60e-9)
    assert out["device_ops"] == [["fusion", pytest.approx(40e-9)],
                                 ["sort", pytest.approx(30e-9)]]
    assert [g[0] for g in out["idle_gaps"]] == ["sync", "dispatch", "sync"]
    assert [g[1] for g in out["idle_gaps"]] == [
        pytest.approx(20e-9), pytest.approx(10e-9), pytest.approx(10e-9)]


def test_reduce_needs_a_window_and_device_ops():
    with pytest.raises(RuntimeError):
        trace.reduce({"devices": {"d": [("op", 0, 1)]}, "host_spans": []})
    with pytest.raises(RuntimeError):
        trace.reduce({"devices": {}, "host_spans": [("window", 0, 1)]})


def test_load_reads_a_recorded_trace(tmp_path):
    """``scopes.load`` on a trace the profiler writes: the harness's host
    spans come back in order on the host clock; the CPU has no TPU
    plane."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("wait"):
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("sync"):
                y.tolist()
    jax.profiler.stop_trace()
    events = scopes.load(str(tmp_path))
    spans = sorted(events["host_spans"], key=lambda s: s[1])
    assert [s[0] for s in spans] == ["window"] + ["dispatch", "wait", "sync"] * 3
    window = spans[0]
    assert all(window[1] <= s <= e <= window[2] for _, s, e in spans[1:])
    assert not any(d.startswith("/device:") for d in events["devices"])
