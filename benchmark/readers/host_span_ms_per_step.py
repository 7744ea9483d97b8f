"""Summed duration of the program's host spans named in ``spans``, over
every host thread of the traced window, in ms per traced step. The spans
are ``TraceAnnotation``s on the profiler's clock, beside the device
events. A run without a device plane (the CPU of the tests), or a program
that records none of these spans, has nothing to read: ``None``."""


def read(run: dict, params: dict):
    trace = run["trace"]
    if trace is None or not trace.device_planes() or not run["traced_steps"]:
        return None
    devices = set(trace.device_planes())
    names = set(params["spans"])
    spans = [dur for plane, _, name, _, dur in trace.events
             if name in names and plane not in devices]
    if not spans:
        return None
    return sum(spans) / 1e6 / run["traced_steps"]
