"""Device self time per round of the traced window under the round
program's scope ``local_sgd`` and those beneath it (the clients' local
SGD through the model)."""
from bench.lib.scopes import layer_ms_per_round


def read(run):
    return None if run.events is None else layer_ms_per_round(
        run.events, ("local_sgd",))
