"""Self time of the harness's ``outer`` spans per step, in ms: their
summed duration less the ``inner`` spans they hold. For ``bench/md_step``
over ``bench/calculate`` that is the driver's own host time a step."""


def read(run: dict, params: dict):
    total = {params["outer"]: 0.0, params["inner"]: 0.0}
    for name, start, end in run["spans"]:
        if name in total:
            total[name] += end - start
    if not run["steps"] or not total[params["inner"]]:
        return None  # the inner span is recorded in a traced run only
    return 1e3 * (total[params["outer"]] - total[params["inner"]]) / run["steps"]
