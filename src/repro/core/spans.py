"""Names of the round's layers in a profiler trace.

Every engine wraps each layer of its round in ``scope(name)``, a
``jax.named_scope``: metadata only, so the compiled program is unchanged
while each XLA op's ``op_name`` carries the path of the layers it came
from (``.../while/body/closed_call/select/topk/...``).  A fusion takes
the ``op_name`` of its root op.  Collectives sit under
``collective_scope(axis)``, i.e. ``collective/<mesh axis>``.

The host side marks the chunk boundary with ``jax.profiler.
TraceAnnotation`` spans whose keyword counters ride in the trace as the
event's stats (DESIGN.md, "Tracing a run").
"""
from __future__ import annotations

import jax

ROUND_SCOPES = ("avail", "budget", "select", "topk", "complete", "cohort",
                "stream", "local_sgd", "aggregate", "server_update",
                "collective")


def scope(name: str):
    """The named scope of one round layer; ``name`` is in ROUND_SCOPES."""
    if name not in ROUND_SCOPES:
        raise ValueError(f"{name!r} is not a round scope {ROUND_SCOPES}")
    return jax.named_scope(name)


def collective_scope(axis: str):
    """The named scope of a collective over the mesh axis ``axis``."""
    return jax.named_scope(f"collective/{axis}")
