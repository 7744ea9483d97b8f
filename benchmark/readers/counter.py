"""A count the harness took around the window (``run["counters"]``)."""


def read(run: dict, params: dict):
    return run["counters"].get(params["counter"])
