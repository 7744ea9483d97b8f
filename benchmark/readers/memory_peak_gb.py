"""Peak bytes in use on the fullest device after the window, in GB."""


def read(run: dict, params: dict):
    return run["memory_peak_bytes"] / 1e9
