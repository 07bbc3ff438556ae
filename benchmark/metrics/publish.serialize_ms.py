"""Weight publish: framing the named leaves into one weight frame on the
publisher thread, per publish; the copy holds the GIL, so the loop cannot
dispatch meanwhile (the program's span `publish.serialize`: its cumulative
seconds over its count, last metrics window of the run's window minus the
first)."""

N, S = "span_publish_serialize_n_total", "span_publish_serialize_s_total"


def read(run):
    syncs = [s[2] for s in run["syncs"] if N in s[2]]
    if len(syncs) < 2 or syncs[-1][N] <= syncs[0][N]:
        return None
    return 1e3 * (syncs[-1][S] - syncs[0][S]) / (syncs[-1][N] - syncs[0][N])
