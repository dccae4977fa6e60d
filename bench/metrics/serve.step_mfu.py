"""Model operations of the serve window's work (``bench/flops.py``) over the
window and the chips' bf16 peak (``bench/peaks.json``)."""


def read(run):
    rec = run["record"]
    if run["traffic"]["driver"] != "serve":
        return None
    return 100.0 * rec["flops"] / rec["window_s"] / run["peak_flops_per_s"]
