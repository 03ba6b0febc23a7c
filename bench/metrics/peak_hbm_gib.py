"""The device's ``peak_bytes_in_use`` read at the window's end, before
the check allocates anything, in GiB: weights, the KV pool, and the
side caches and activations set-up and the window needed."""


def read(run):
    return run.peak_bytes / 2 ** 30 if run.peak_bytes else None
