"""Device self time per round of the traced window under the round
program's scopes ``aggregate`` and ``server_update`` (aggregation of the
clients' deltas and the server step)."""
from bench.lib.scopes import layer_ms_per_round


def read(run):
    return None if run.events is None else layer_ms_per_round(
        run.events, ("aggregate", "server_update"))
