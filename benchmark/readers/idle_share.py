"""Idle share of the busiest device over the traced window, in %: 1 less
the union of its operations' intervals over the window from the first
device operation's start to the last one's end."""


def read(run: dict, params: dict):
    trace = run["trace"]
    if trace is None or not trace.device_planes():
        return None
    t0, t1 = trace.window()
    return 100.0 * (1.0 - trace.busy_ns(trace.busiest_plane()) / (t1 - t0))
