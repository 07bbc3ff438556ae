"""Learner loop: the share of dispatches that found no step in flight, so
that the device may have had nothing queued when the step was handed over
(the program's `loop_starved_n_total` over `span_loop_dispatch_n_total`,
last metrics window of the run's window minus the first)."""

STEPS = "span_loop_dispatch_n_total"
STARVED = "loop_starved_n_total"


def read(run):
    syncs = [s[2] for s in run["syncs"] if STARVED in s[2] and STEPS in s[2]]
    if len(syncs) < 2 or syncs[-1][STEPS] <= syncs[0][STEPS]:
        return None
    return 100.0 * (syncs[-1][STARVED] - syncs[0][STARVED]) / (syncs[-1][STEPS] - syncs[0][STEPS])
