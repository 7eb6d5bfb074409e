"""Peak device memory of the training run, in GB: the allocator's
``peak_bytes_in_use`` after the window."""


def read(run, red):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
