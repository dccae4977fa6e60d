"""Share of the window the training loop spent waiting in ``next(feed)``
for the plan's next device batch: the harness's clock around each call."""


def read(run):
    rec = run["record"]
    if run["traffic"]["driver"] != "train":
        return None
    return 100.0 * rec["feed_wait_s"] / rec["window_s"]
