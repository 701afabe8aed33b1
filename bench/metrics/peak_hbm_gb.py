"""Peak device memory in use after the window, on the fullest chip, in
GB (1e9 bytes), as the device's allocator reports it
(``memory_stats()['peak_bytes_in_use']``)."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
