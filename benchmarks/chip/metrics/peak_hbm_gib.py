"""The runtime's peak bytes in use on the chip after the window."""


def read(run):
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / 2 ** 30
