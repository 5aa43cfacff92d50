"""Executions of programs other than the superstep inside the traced
periods, per period: the small eager programs the host queues between two
supersteps."""


def read(run):
    red, trace = run.get("reduced"), run.get("trace")
    if not red or not trace or not red["n_modules"]:
        return None
    lo, hi = red["window"]
    counts = [sum(1 for n, s, _ in dev["modules"] if "superstep" not in n and lo <= s < hi)
              for dev in trace["devices"].values()]
    return sum(counts) / len(counts) / red["n_modules"]
