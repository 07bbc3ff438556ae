"""Weight publish: the publisher thread's wait, inside `publish.d2h`, until
the device has run every step dispatched before the flatten program and
the flatten itself: the host's lead over the device, not a copy, per
publish (the program's span `publish.ready_wait`: its cumulative seconds
over its count, last metrics window of the run's window minus the first)."""

N, S = "span_publish_ready_wait_n_total", "span_publish_ready_wait_s_total"


def read(run):
    syncs = [s[2] for s in run["syncs"] if N in s[2]]
    if len(syncs) < 2 or syncs[-1][N] <= syncs[0][N]:
        return None
    return 1e3 * (syncs[-1][S] - syncs[0][S]) / (syncs[-1][N] - syncs[0][N])
