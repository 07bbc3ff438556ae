"""Broker pop and staging pack: the prefetch lane's wait for a packed
batch, per step (the program's `time_wait_batch_s`, averaged over the
window's metric windows)."""


def read(run):
    vals = [s[2]["time_wait_batch_s"] for s in run["syncs"] if "time_wait_batch_s" in s[2]]
    if not vals:
        return None
    return 1e3 * sum(vals) / len(vals)
