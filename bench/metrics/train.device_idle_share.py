"""Share of the traced slice of a train window in which no operation ran
on the device: 1 - busy union / slice (profiler trace)."""


def read(run):
    trace = run["trace"]
    if run["traffic"]["driver"] != "train" or not trace or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
