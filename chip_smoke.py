#!/usr/bin/env python3
"""Smoke run of the federated round engine on a TPU.

    python chip_smoke.py               # one chip: phases `paper`, `million`
    python chip_smoke.py --four-chips  # four chips: the client-sharded
                                       # engine on (4,) and (2, 2) meshes
                                       # against the one-device engine

Phase ``paper`` runs ``run_scenario(RunSpec(...))`` on the Shakespeare
char-LSTM at the paper's widths (100 clients, M = 10, E = 5, batch 4,
``homedevices`` availability) on the device engine, once with each
``select_impl``, and checks that the masks and r_k agree bit for bit and
that the ``pallas`` run used the compiled kernel.  Phase ``million`` runs
N = 1 000 000 clients (f3ast, bernoulli q = 0.3, K = 10, on-demand
``SynthTask`` cohorts) for four rounds, then checks the compiled
``fed_select`` at ``MAX_KERNEL_N`` against the unfused XLA pipeline.

Each phase prints one JSON line (compile seconds and steady seconds per
round are informational, never a claim); the last line of stdout is
``{"ok": true, "device": {...}}``.  Any failed check raises and the script
exits non-zero.  Without a TPU, or run outside a checkout of the
repository, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
PAPER_ROUNDS, MILLION_N, CHUNK = 4, 1_000_000, 2


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _log(*args, **kwargs) -> None:
    print(*args, file=sys.stderr, **kwargs)


def _peak_bytes() -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def _ulps(got, want) -> int:
    """Largest distance in units in the last place between two f32 arrays."""
    a = np.asarray(got, np.float32).view(np.int32).astype(np.int64)
    b = np.asarray(want, np.float32).view(np.int32).astype(np.int64)
    a = np.where(a < 0, -(2**31) - a, a)      # sign-magnitude -> ordered
    b = np.where(b < 0, -(2**31) - b, b)
    return int(np.abs(a - b).max()) if a.size else 0


# ---------------------------------------------------------------------------
# Phase `paper`: the paper's Shakespeare task through run_scenario
# ---------------------------------------------------------------------------

def phase_paper(*, seed: int = 0, rounds: int = PAPER_ROUNDS,
                chunk_size: int = CHUNK,
                expect_path: str = "compiled") -> dict:
    from repro.sim import RunSpec, Scenario, run_scenario

    scenario = Scenario(name="homedevices", availability="homedevices",
                        task="shakespeare")
    runs, secs = {}, {}
    for impl in ("xla", "pallas"):
        spec = RunSpec(scenario=scenario, strategy="f3ast", rounds=rounds,
                       chunk_size=chunk_size, eval_every=rounds,
                       engine="device", select_impl=impl, seed=seed)
        t0 = time.perf_counter()
        runs[impl] = run_scenario(spec, log_fn=_log)
        secs[impl] = time.perf_counter() - t0
    xla, pallas = runs["xla"], runs["pallas"]
    for impl, res in runs.items():
        fm = res.final_metrics
        check(fm["engine"] == "device", f"{impl}: engine {fm['engine']!r}")
        losses = [h["train_loss"] for h in res.history] + [fm["test_loss"]]
        check(np.isfinite(losses).all(), f"{impl}: losses {losses}")
    check(xla.final_metrics["select_path"] == "xla",
          f"xla run took {xla.final_metrics['select_path']!r}")
    path = pallas.final_metrics["select_path"]
    check(path == expect_path,
          f"pallas run took select path {path!r}, expected {expect_path!r}")
    check(np.array_equal(xla.sel_history, pallas.sel_history),
          "selection masks differ between select_impl xla and pallas")
    check(xla.rates.tobytes() == pallas.rates.tobytes(),
          f"final r_k differ: {_ulps(pallas.rates, xla.rates)} ulp")
    steady = {impl: runs[impl].final_metrics.get("steady_rounds_per_s")
              for impl in runs}
    return dict(phase="paper", task="shakespeare", n_clients=100,
                rounds=rounds, chunk_size=chunk_size, select_path=path,
                masks_equal=True, rates_bitwise_equal=True,
                n_selected=int(xla.sel_history.sum()),
                test_loss=float(xla.final_metrics["test_loss"]),
                wall_s=secs,
                steady_s_per_round={k: (1.0 / v if v else None)
                                    for k, v in steady.items()},
                peak_bytes_in_use=_peak_bytes())


# ---------------------------------------------------------------------------
# Phase `million`: deployment-size client state on one chip
# ---------------------------------------------------------------------------

def _run_engine(engine, *, seed: int, rounds: int, chunk_size: int) -> dict:
    """Drive ``engine`` for ``rounds`` rounds; masks, losses, final r_k,
    first-chunk (compile) and steady per-round seconds."""
    from repro.sim.engine import _final_rates, _unpack_stream

    carry = engine.init_carry(jax.random.PRNGKey(seed))
    masks, losses, chunk_s, last_out = [], [], [], None
    for t0 in range(0, rounds, chunk_size):
        ts = jnp.arange(t0, min(t0 + chunk_size, rounds), dtype=jnp.int32)
        start = time.perf_counter()
        carry, out = engine.chunk(carry, ts)
        jax.block_until_ready(out)
        chunk_s.append(time.perf_counter() - start)
        last_out = out
        out_np = _unpack_stream(jax.tree.map(np.asarray, out),
                                engine.n_clients)
        masks.append(out_np.sel_mask)
        losses.append(out_np.train_loss)
    steady = chunk_s[1:]
    return dict(carry=carry, stream=last_out,
                masks=np.concatenate(masks), losses=np.concatenate(losses),
                rates=_final_rates(engine, carry, engine.n_clients),
                compile_s=chunk_s[0],
                steady_s_per_round=(sum(steady) / (rounds - chunk_size)
                                    if steady else None))


def _million_engine(n_clients: int, mesh=None, *, seed: int,
                    model_axis=None):
    if str(ROOT) not in sys.path:          # benchmarks/ is not a package
        sys.path.insert(0, str(ROOT))
    from benchmarks.bench_engine import _build_nscale_engine
    return _build_nscale_engine(n_clients, mesh, synth=True, seed=seed,
                                model_axis=model_axis)


def _kernel_check(n: int, *, seed: int, expect_path: str) -> dict:
    """Fused ``fed_select`` vs the unfused ``_topk_mask → update_rates →
    weight rule`` pipeline, both compiled on the same device, every weight
    rule, a tie-heavy and a tie-free score field."""
    from repro.core import aggregation, selection
    from repro.core.hfun import R_MIN
    from repro.core.rates import RateState, update_rates
    from repro.kernels import fed_select as fs
    from repro.kernels.ref import SELECT_WEIGHT_MODES

    path = fs.dispatch_mode(n)
    check(path == expect_path,
          f"fed_select at N={n} takes {path!r}, expected {expect_path!r}")
    beta = 1e-3

    @functools.partial(jax.jit, static_argnames=("weight_mode",))
    def unfused(scores, avail, k, r, p, rw, *, weight_mode):
        mask = selection._topk_mask(scores, avail, k)
        new_r = update_rates(RateState(r=r, t=jnp.zeros((), jnp.int32)),
                             mask, beta).r
        if weight_mode == "unbiased":
            w = aggregation.unbiased_weights(p, jnp.maximum(new_r, R_MIN),
                                             mask)
        elif weight_mode == "unbiased_frozen":
            w = aggregation.unbiased_weights(p, rw, mask)
        elif weight_mode == "uniform":
            w = aggregation.uniform_weights(mask)
        else:
            w = aggregation.fedavg_weights(p, mask)
        return mask, new_r, w

    rng = np.random.default_rng(seed)
    avail = jnp.asarray(rng.random(n) < 0.3)
    r = jnp.asarray(rng.random(n).astype(np.float32))
    p = jnp.asarray(np.full(n, 1.0 / n, np.float32))
    rw = jnp.asarray((rng.random(n) * 0.9 + 0.05).astype(np.float32))
    fields = {"distinct": rng.normal(size=n).astype(np.float32),
              "ties": rng.integers(0, 4, n).astype(np.float32)}
    ulps = {}
    for field, scores in fields.items():
        scores = jnp.asarray(scores)
        for k in (10, 1000):
            kk = jnp.asarray(k, jnp.int32)
            want_mask = np.asarray(selection._topk_mask(scores, avail, kk))
            got_mask = np.asarray(fs.fed_select_mask(scores, avail, kk))
            check(np.array_equal(got_mask, want_mask),
                  f"fed_select_mask {field} k={k}: masks differ")
            for mode in SELECT_WEIGHT_MODES:
                want = unfused(scores, avail, kk, r, p, rw, weight_mode=mode)
                got = fs.fed_select(
                    scores, avail, kk, r, p, beta, weight_mode=mode,
                    r_weight=rw if mode == "unbiased_frozen" else None)
                check(np.array_equal(np.asarray(got[0]),
                                     np.asarray(want[0])),
                      f"fed_select {field} k={k} {mode}: masks differ")
                check(int(np.asarray(got[0]).sum()) == min(
                    k, int(np.asarray(avail).sum())),
                      f"fed_select {field} k={k} {mode}: cohort size")
                for name, g, w in zip(("r", "w"), got[1:], want[1:]):
                    d = _ulps(g, w)
                    key = f"{name}_{mode}"
                    ulps[key] = max(ulps.get(key, 0), d)
                    check(d <= 1, f"fed_select {field} k={k} {mode} {name}: "
                                  f"{d} ulp from the unfused pipeline")
    return dict(n=n, select_path=path, masks_equal=True, max_ulps=ulps)


def phase_million(*, seed: int = 0, n_clients: int = MILLION_N,
                  rounds: int = PAPER_ROUNDS, chunk_size: int = CHUNK,
                  kernel_n: int | None = None,
                  expect_path: str = "compiled") -> dict:
    from repro.kernels.fed_select import MAX_KERNEL_N

    run = _run_engine(_million_engine(n_clients, seed=seed), seed=seed,
                      rounds=rounds, chunk_size=chunk_size)
    check(np.isfinite(run["losses"]).all(), f"losses {run['losses']}")
    r = run["rates"]
    check(np.isfinite(r).all() and r.min() >= 0.0 and r.max() <= 1.0,
          f"final r_k outside [0, 1]: [{r.min()}, {r.max()}]")
    kern = _kernel_check(kernel_n or MAX_KERNEL_N, seed=seed,
                         expect_path=expect_path)
    return dict(phase="million", n_clients=n_clients, rounds=rounds,
                chunk_size=chunk_size,
                train_loss=[float(x) for x in run["losses"]],
                n_selected=int(run["masks"].sum()),
                r_range=[float(r.min()), float(r.max())],
                compile_s=run["compile_s"],
                steady_s_per_round=run["steady_s_per_round"],
                kernel=kern, peak_bytes_in_use=_peak_bytes())


# ---------------------------------------------------------------------------
# --four-chips: the client-sharded engine against the one-device engine
# ---------------------------------------------------------------------------

def _client_axis_devices(tree, axis: str) -> list:
    """Distinct devices holding each array whose sharding names ``axis``."""
    spans = []
    for leaf in jax.tree.leaves(tree):
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        if spec is not None and axis in jax.tree.leaves(tuple(spec)):
            spans.append(len({s.device for s in leaf.addressable_shards}))
    return spans


def phase_four_chips(*, seed: int = 0, n_clients: int = MILLION_N,
                     rounds: int = PAPER_ROUNDS,
                     chunk_size: int = CHUNK) -> dict:
    from repro.launch.mesh import make_fed_mesh

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    runs = {"1": _run_engine(_million_engine(n_clients, seed=seed),
                             seed=seed, rounds=rounds, chunk_size=chunk_size)}
    for label, shape, model_axis in (("(4,)", (4,), None),
                                     ("(2,2)", (2, 2), "model")):
        engine = _million_engine(n_clients, make_fed_mesh(shape), seed=seed,
                                 model_axis=model_axis)
        runs[label] = _run_engine(engine, seed=seed, rounds=rounds,
                                  chunk_size=chunk_size)
    ref = runs["1"]
    out = dict(phase="four_chips", n_clients=n_clients, rounds=rounds,
               chunk_size=chunk_size, meshes={})
    for label, run in runs.items():
        check(np.isfinite(run["losses"]).all(),
              f"{label}: losses {run['losses']}")
        check(np.array_equal(run["masks"], ref["masks"]),
              f"{label}: selection masks differ from one chip")
        check(run["rates"].tobytes() == ref["rates"].tobytes(),
              f"{label}: final r_k differ from one chip by "
              f"{_ulps(run['rates'], ref['rates'])} ulp")
        entry = dict(compile_s=run["compile_s"],
                     steady_s_per_round=run["steady_s_per_round"],
                     train_loss=[float(x) for x in run["losses"]])
        if label != "1":
            spans = _client_axis_devices((run["carry"], run["stream"]),
                                         "clients")
            check(spans and min(spans) == 4,
                  f"{label}: client-axis arrays span {spans} devices")
            entry["client_axis_devices"] = spans
        out["meshes"][label] = entry
    # the shard must not sit on device 0 alone (the CPU backend keeps no
    # per-device memory statistics, so this reads only on the chip)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:4]]
    if jax.devices()[0].platform == "tpu":
        check(all(b and b > 0 for b in in_use),
              f"bytes in use per device: {in_use}")
    out.update(masks_equal=True, rates_bitwise_equal=True,
               bytes_in_use=in_use, peak_bytes_in_use=_peak_bytes())
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the client-sharded engine on (4,) and "
                         "(2, 2) meshes against the one-device engine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _log(f"chip_smoke: no repository around {ROOT} (src/repro missing); "
             f"run it from a checkout")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r} "
             f"({devices[0].device_kind}); nothing was run")
        return 1
    cache = use_compile_cache()
    dev = dict(platform=devices[0].platform, kind=devices[0].device_kind,
               count=len(devices))
    print(json.dumps(dict(devices=dev, compile_cache=cache)), flush=True)
    if args.four_chips:
        phases = (phase_four_chips,)
    else:
        phases = (phase_paper, phase_million)
    for phase in phases:
        print(json.dumps(phase(seed=args.seed)), flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
