"""Device self time per round of the traced window under the round
program's scopes ``avail`` and ``budget`` (the availability draw and K_t
budget)."""
from bench.lib.scopes import layer_ms_per_round


def read(run):
    return None if run.events is None else layer_ms_per_round(
        run.events, ("avail", "budget"))
