"""Peak device memory after the window, on the fullest chip."""


def read(run):
    return run["peak_bytes"] / 2 ** 30 if run["peak_bytes"] else None
