"""``benchmarks/bench_engine.py`` records only a genuine out-of-memory as
``oom``; any other failure — a compile refusal among them — propagates."""
import importlib.util
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_engine", ROOT / "benchmarks" / "bench_engine.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _failing(exc):
    def build(*args, **kwargs):
        raise exc
    return build


@pytest.mark.parametrize("exc", [
    jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: Out of memory while "
                               "trying to allocate 17179869184 bytes."),
    MemoryError("host allocation failed"),
])
def test_resource_exhaustion_is_recorded_as_oom(bench, monkeypatch, exc):
    monkeypatch.setattr(bench, "_build_nscale_engine", _failing(exc))
    out = bench.bench_nscale([(1_000, "synth", ("device",), None)], 2, 1)
    assert out["cells"][0]["device"]["status"] == "oom"


@pytest.mark.parametrize("exc", [
    jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU "
                               "kernel: unsupported shape cast"),
    NotImplementedError("Unimplemented primitive in Pallas TPU lowering"),
])
def test_other_errors_propagate(bench, monkeypatch, exc):
    monkeypatch.setattr(bench, "_build_nscale_engine", _failing(exc))
    with pytest.raises(type(exc)):
        bench.bench_nscale([(1_000, "synth", ("device",), None)], 2, 1)
