"""Learner loop: the loop thread's exposed wait on the prefetch lane per
step (the program's `pipeline_device_idle_s`, averaged over the window's
metric windows)."""


def read(run):
    vals = [s[2]["pipeline_device_idle_s"] for s in run["syncs"] if "pipeline_device_idle_s" in s[2]]
    if not vals:
        return None
    return 1e3 * sum(vals) / len(vals)
