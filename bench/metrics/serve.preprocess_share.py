"""Share of the window spent in the row program, as the serving loop's own
``ServeStats.preprocess_s`` counts it."""


def read(run):
    rec = run["record"]
    if run["traffic"]["driver"] != "serve":
        return None
    return 100.0 * rec["preprocess_s"] / rec["window_s"]
