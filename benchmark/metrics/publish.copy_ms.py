"""Weight publish: the rest of `publish.d2h` once the flat buffer is ready on
the device: the device-to-host copy of the frame's bytes and its split into
named leaves, per publish (the program's span `publish.copy`: its
cumulative seconds over its count, last metrics window of the run's window
minus the first)."""

N, S = "span_publish_copy_n_total", "span_publish_copy_s_total"


def read(run):
    syncs = [s[2] for s in run["syncs"] if N in s[2]]
    if len(syncs) < 2 or syncs[-1][N] <= syncs[0][N]:
        return None
    return 1e3 * (syncs[-1][S] - syncs[0][S]) / (syncs[-1][N] - syncs[0][N])
