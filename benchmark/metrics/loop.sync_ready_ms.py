"""Learner loop: the part of `loop.sync` that waits for the newest dispatched
step to complete on the device (the queue the loop ran ahead by), per sync
(the program's span `loop.sync_ready`: its cumulative seconds over its
count, last metrics window of the run's window minus the first)."""

N, S = "span_loop_sync_ready_n_total", "span_loop_sync_ready_s_total"


def read(run):
    syncs = [s[2] for s in run["syncs"] if N in s[2]]
    if len(syncs) < 2 or syncs[-1][N] <= syncs[0][N]:
        return None
    return 1e3 * (syncs[-1][S] - syncs[0][S]) / (syncs[-1][N] - syncs[0][N])
