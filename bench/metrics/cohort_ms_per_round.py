"""Device self time per round of the traced window under the round
program's scope ``cohort`` (cohort ids from the mask and the cohort's
data)."""
from bench.lib.scopes import layer_ms_per_round


def read(run):
    return None if run.events is None else layer_ms_per_round(
        run.events, ("cohort",))
