"""Peak bytes in use on the fullest chip after the window, as the device
allocator reports it (``memory_stats()["peak_bytes_in_use"]``)."""


def read(run):
    return run.peak_bytes or None
