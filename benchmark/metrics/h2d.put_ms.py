"""H2D: the host's time in the prefetch lane's `device_put` of one batch
(the program's `time_device_put_s`, averaged over the window's metric
windows). The call returns once the transfer is handed over, so this is
what the put costs the host, unfenced, as the timed path runs it."""


def read(run):
    vals = [s[2]["time_device_put_s"] for s in run["syncs"] if "time_device_put_s" in s[2]]
    if not vals:
        return None
    return 1e3 * sum(vals) / len(vals)
