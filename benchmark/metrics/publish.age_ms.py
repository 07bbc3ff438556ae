"""Weight publish: from the version's weights existing on the device
(`publish.ready_wait`'s exit) to that version sent: how old the weights are
when an actor can first read them. `publish.latency_ms` counts from the
loop's submit, which runs ahead of the device (the program's
`publish.age`: its cumulative seconds over its count, last metrics window
of the run's window minus the first)."""

N, S = "span_publish_age_n_total", "span_publish_age_s_total"


def read(run):
    syncs = [s[2] for s in run["syncs"] if N in s[2]]
    if len(syncs) < 2 or syncs[-1][N] <= syncs[0][N]:
        return None
    return 1e3 * (syncs[-1][S] - syncs[0][S]) / (syncs[-1][N] - syncs[0][N])
