"""Device time of one decode step: the ``jit_serve_step`` program's time
in the traced slice over its count (profiler trace). Absent where the
traffic asks for one token and no decode step runs."""


def read(run):
    trace = run["trace"]
    if run["traffic"]["driver"] != "serve" or not trace:
        return None
    seconds, count = trace["modules"].get("jit_serve_step", (0.0, 0))
    return 1e3 * seconds / count if count else None
