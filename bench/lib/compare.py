"""The comparison that decides ``correct``.

The system's first chunk of rounds (driven through the window's own
``DeviceEngine.chunk`` call in set-up) against the plain reference over
the same rounds:

* ``draws_wrong``      rounds whose K_t or count of available clients
                       differs (exact: limit 0);
* ``cohort_wrong``     rounds whose selected or completed mask differs
                       (exact: limit 0);
* ``rate_gap``         largest relative gap of the rate EMA r_k after the
                       chunk;
* ``loss_gap``         largest relative gap of a round's training loss;
* ``update_norm_gap``  largest relative gap of a round's server update
                       norm |Delta_t|, as the server optimizer got it;
* ``param_change_gap`` the worst leaf's gap between the norms of the
                       parameters' change over the chunk, against the
                       larger of that leaf's reference norm and the median
                       leaf's; leaves the reference leaves unmoved (change
                       under 1e-3 of the median leaf's) are left out.

Each number has its own limit in ``bench/limits/<workload>.json``, set
from the readings listed there.
"""
from __future__ import annotations

import jax
import numpy as np


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(a - b) / np.abs(b)
    return float(np.nanmax(np.where(np.isfinite(a), gap, np.inf)))


def param_change_gap(prog0, prog1, ref0, ref1) -> float:
    norm = lambda a, b: float(np.linalg.norm(  # noqa: E731
        np.asarray(b, np.float64) - np.asarray(a, np.float64)))
    got = [norm(a, b) for a, b in zip(jax.tree.leaves(prog0),
                                        jax.tree.leaves(prog1))]
    want = [norm(a, b) for a, b in zip(jax.tree.leaves(ref0),
                                         jax.tree.leaves(ref1))]
    median = float(np.median(want))
    gaps = [abs(g - w) / max(w, median)
            for g, w in zip(got, want) if w >= 1e-3 * median]
    return float(max(gaps)) if all(np.isfinite(got)) else float("inf")


def numbers(prog: dict, ref: dict) -> dict:
    """``prog``: the system's decoded stream of the chunk (``stream``), its
    parameters before and after (``params0``, ``params``) and its rates
    after (``r``); ``ref``: :func:`reference.run_reference`'s output."""
    s = prog["stream"]
    draws = ((np.asarray(s.k_t) != ref["k_t"])
             | (np.asarray(s.n_available) != ref["n_available"]))
    cohort = ((s.sel_mask != ref["sel"]).any(axis=1)
              | (s.completed != ref["sel"]).any(axis=1))
    return {
        "draws_wrong": int(draws.sum()),
        "cohort_wrong": int(cohort.sum()),
        "rate_gap": _rel(prog["r"], ref["r"]),
        "loss_gap": _rel(s.train_loss, ref["loss"]),
        "update_norm_gap": _rel(s.delta_norm, ref["delta_norm"]),
        "param_change_gap": param_change_gap(prog["params0"], prog["params"],
                                             ref["params0"], ref["params"]),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) — every limited number must
    be at or under its limit."""
    checks = {name: {"value": values[name], "limit": lim["limit"]}
              for name, lim in limits.items() if name in values}
    missing = sorted(set(limits) - set(values)) + sorted(set(values) - set(limits))
    ok = not missing and all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
