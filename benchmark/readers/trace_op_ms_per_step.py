"""Summed device time of the operations whose name matches ``pattern``, on
the busiest device of the traced steps, in ms per step."""


def read(run: dict, params: dict):
    trace = run["trace"]
    if trace is None or not trace.device_planes() or not run["traced_steps"]:
        return None
    count, ns = trace.sum_matching(trace.busiest_plane(), params["pattern"])
    if not count:
        return None
    return ns / 1e6 / run["traced_steps"]
