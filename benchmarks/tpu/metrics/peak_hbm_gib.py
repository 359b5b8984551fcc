"""``peak_bytes_in_use`` of the chip after the window, before the
correctness check allocates, in GiB."""


def read(run):
    return run.peak_bytes / 2 ** 30 if run.peak_bytes else None
