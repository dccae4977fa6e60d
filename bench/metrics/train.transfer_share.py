"""Share of the window the feed spent in its host-to-device transfers (the
program's ``feed.transfer`` spans, which the feed adds to the plan's stats
dict as ``transfer_s``; the few transfers of set-up's checked steps are
included). None where the program keeps no count."""


def read(run):
    if run["traffic"]["driver"] != "train":
        return None
    rec = run["record"]
    seconds = (rec.get("feed_stats") or {}).get("transfer_s")
    return None if seconds is None else 100.0 * seconds / rec["window_s"]
