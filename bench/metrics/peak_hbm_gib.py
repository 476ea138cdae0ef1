"""Peak device memory in use after the window, set-up included."""


def read(run):
    return run.peak_bytes / 2 ** 30
