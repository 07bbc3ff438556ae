"""Learner loop: the part of `loop.sync` after the device is done: the
`device_get` of the step's few scalars, per sync. Milliseconds at most; a
run that holds a stall of a second here lost it on the host with the device
idle (the program's span `loop.sync_get`: its cumulative seconds over its
count, last metrics window of the run's window minus the first)."""

N, S = "span_loop_sync_get_n_total", "span_loop_sync_get_s_total"


def read(run):
    syncs = [s[2] for s in run["syncs"] if N in s[2]]
    if len(syncs) < 2 or syncs[-1][N] <= syncs[0][N]:
        return None
    return 1e3 * (syncs[-1][S] - syncs[0][S]) / (syncs[-1][N] - syncs[0][N])
