"""Share of the window the plan spent starting epochs: from each epoch's
executor start to its first batch (the program's ``plan.epoch_start``
spans, which the plan counts in its stats dict as ``epochs`` and
``epoch_start_s``). The dict spans the feed's whole life and its first
epoch starts in set-up, so the window is given the mean start time of
the epochs begun after that one. None where the program keeps no count."""


def read(run):
    if run["traffic"]["driver"] != "train":
        return None
    rec = run["record"]
    stats = rec.get("feed_stats") or {}
    epochs = stats.get("epochs", 0)
    if not epochs:
        return None
    return 100.0 * stats["epoch_start_s"] * (epochs - 1) / epochs / rec["window_s"]
