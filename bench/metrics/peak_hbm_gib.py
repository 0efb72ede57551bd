"""Peak device memory of the run, ``memory_stats()["peak_bytes_in_use"]``
read after the window, in GiB."""


def read(rec):
    return rec.memory_peak_bytes / 2 ** 30 if rec.memory_peak_bytes else None
