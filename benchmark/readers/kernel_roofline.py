"""A kernel's share of its roofline over the traced steps, in %.

The least time the chip could take for the calls of one step is the larger
of the operations they need over the peak rate and the bytes they need over
the memory bandwidth (the family's ``kernel_work``: rows in, rows out and
ids of the real rows, once per step, however often the program recomputes
them). That, over the summed device time per step of the operations whose
name matches ``pattern`` on the busiest device.
"""


def read(run: dict, params: dict):
    trace, peaks = run["trace"], run["peaks"]
    work = run["kernel_work"].get(params["kernel"])
    if (trace is None or not trace.device_planes() or peaks is None
            or not work or not run["traced_steps"]):
        return None
    count, ns = trace.sum_matching(trace.busiest_plane(), params["pattern"])
    if not count:
        return None
    least_s = max(work["flops"] / peaks["flops_per_s"],
                  work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ns / 1e9 / run["traced_steps"])
