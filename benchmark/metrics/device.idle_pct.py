"""Device: 1 - union of busy intervals over the traced window, device
trace (mean over the chips). Chip runs only."""


def read(run):
    tr = run["trace"]
    if not tr or run["device"]["platform"] != "tpu":
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
