"""The whole step's share of the chips' peak, in %: the operations one
energy-and-forces step needs over real atoms and real edges (counted by the
family's ``step_flops``: forward and backward, nothing padded, nothing
recomputed), times steps per second over the whole window, over chips
times the peak."""


def read(run: dict, params: dict):
    if run["peaks"] is None or not run["steps"]:
        return None
    rate = run["flops_per_step"] * run["steps"] / run["window_s"]
    return 100.0 * rate / (run["chips"] * run["peaks"]["flops_per_s"])
