"""Device time of one training step: the ``jit_step`` program's time in the
traced slice over its count (profiler trace)."""


def read(run):
    trace = run["trace"]
    if run["traffic"]["driver"] != "train" or not trace:
        return None
    seconds, count = trace["modules"].get("jit_step", (0.0, 0))
    return 1e3 * seconds / count if count else None
