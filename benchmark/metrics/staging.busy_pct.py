"""Broker pop and staging pack: share of the time the staging thread spends
decoding and admitting frames and packing batches (the program's spans
`staging.ingest` and `staging.pack`: cumulative seconds, last metrics window
of the run's window minus the first, over the time between those two)."""

KEYS = ("span_staging_ingest_s_total", "span_staging_pack_s_total")


def read(run):
    syncs = [s for s in run["syncs"] if all(k in s[2] for k in KEYS)]
    if len(syncs) < 2 or syncs[-1][0] <= syncs[0][0]:
        return None
    busy = sum(syncs[-1][2][k] - syncs[0][2][k] for k in KEYS)
    return 100.0 * busy / (syncs[-1][0] - syncs[0][0])
