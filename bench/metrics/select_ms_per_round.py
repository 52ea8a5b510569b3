"""Device self time per round of the traced window under the round
program's scope ``select`` and those beneath it (the selection strategy
and its top-k cut)."""
from bench.lib.scopes import layer_ms_per_round


def read(run):
    return None if run.events is None else layer_ms_per_round(
        run.events, ("select",))
