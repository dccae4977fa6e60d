"""Set-up time: from the process's start to the window, loading, the
traffic driver's set-up, warm-up and every compilation included (host clock)."""


def read(run):
    return run["setup_s"]
