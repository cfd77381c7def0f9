"""The device allocator's peak bytes in use, read after the window and
before the reference check allocates anything."""


def read(run):
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / 1e9
