"""Peak device memory in use over the run, read after the window, on the
fullest chip (``memory_stats()["peak_bytes_in_use"]``)."""


def read(w):
    return w.memory_peak_bytes / 1e9 if w.memory_peak_bytes else None
