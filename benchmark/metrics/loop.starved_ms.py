"""Learner loop: for how long, per step, the device may have had nothing
queued: at each dispatch that found no step in flight, the time since the
loop last knew the device busy (its previous dispatch: an upper bound) or
done (the metrics sync: exact), whatever the loop was doing (the program's
five `loop_starved_<cause>_s_total` over `span_loop_dispatch_n_total`, last
metrics window of the run's window minus the first)."""

STEPS = "span_loop_dispatch_n_total"
CAUSES = tuple(f"loop_starved_{c}_s_total" for c in ("take", "sync", "publish", "checkpoint", "other"))


def read(run):
    syncs = [s[2] for s in run["syncs"] if CAUSES[0] in s[2] and STEPS in s[2]]
    if len(syncs) < 2 or syncs[-1][STEPS] <= syncs[0][STEPS]:
        return None
    return 1e3 * sum(syncs[-1][k] - syncs[0][k] for k in CAUSES) / (syncs[-1][STEPS] - syncs[0][STEPS])
