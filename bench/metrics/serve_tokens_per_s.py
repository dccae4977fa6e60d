"""Generated tokens of the window's requests over the time from the first
request's due time to the last request's last token (host clock)."""


def read(run):
    rec = run["record"]
    if run["traffic"]["driver"] != "serve":
        return None
    return rec["tokens"] / rec["window_s"]
