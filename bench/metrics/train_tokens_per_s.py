"""Non-pad encoder and decoder tokens of the steps completed in the window,
over the window (host clock)."""


def read(run):
    rec = run["record"]
    if run["traffic"]["driver"] != "train":
        return None
    return rec["tokens"] / rec["window_s"]
