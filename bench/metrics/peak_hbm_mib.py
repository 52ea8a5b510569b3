"""Peak device memory in use by the process after the window
(``memory_stats()["peak_bytes_in_use"]`` of the fullest chip), in MiB."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2**20
