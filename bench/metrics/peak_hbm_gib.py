"""Peak device memory in use (``peak_bytes_in_use`` of the fullest chip,
read after the window), in GiB."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2**30
