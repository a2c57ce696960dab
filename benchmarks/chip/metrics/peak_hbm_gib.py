"""Peak device memory in use after the window (the allocator's
``peak_bytes_in_use`` on the fullest chip), in GiB."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2**30
