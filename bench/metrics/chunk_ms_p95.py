"""95th percentile, over every chunk of the window, of the host time from
dispatching the chunk to its decoded stream."""
import statistics


def read(run):
    if len(run.chunk_ms) < 20:
        return None
    return statistics.quantiles(run.chunk_ms, n=20, method="inclusive")[18]
