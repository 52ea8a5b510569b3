"""Host time per round in the program's ``stream_decode`` spans of the
traced window: unpacking a chunk's packed cohort masks to bool
(``sim/engine.py::_unpack_stream``), inside the harness's ``sync``."""
from bench.lib.scopes import span_ms_per_round


def read(run):
    return None if run.events is None else span_ms_per_round(
        run.events, "stream_decode")
