"""Learner loop: the most steps any dispatch found ahead of it on the device,
dispatched and not yet complete (the program's `loop_inflight_max`, a gauge
of each metrics window: the largest over the run's window, the first
metrics window left out because it began before the window opened)."""

KEY = "loop_inflight_max"


def read(run):
    vals = [s[2][KEY] for s in run["syncs"] if KEY in s[2]]
    if len(vals) < 2:
        return None
    return max(vals[1:])
