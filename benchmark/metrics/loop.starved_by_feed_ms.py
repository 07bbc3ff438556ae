"""Learner loop: the part of `loop.starved_ms` in which the lane had no batch:
dispatches that found no step in flight after the loop spent most of the
time since its previous one waiting in `loop.take`. Unlike
`feed.exposed_wait_ms` it counts no wait during which steps were queued
(the program's `loop_starved_take_s_total` over
`span_loop_dispatch_n_total`, last metrics window of the run's window minus
the first)."""

STEPS = "span_loop_dispatch_n_total"
STARVED = "loop_starved_take_s_total"


def read(run):
    syncs = [s[2] for s in run["syncs"] if STARVED in s[2] and STEPS in s[2]]
    if len(syncs) < 2 or syncs[-1][STEPS] <= syncs[0][STEPS]:
        return None
    return 1e3 * (syncs[-1][STARVED] - syncs[0][STARVED]) / (syncs[-1][STEPS] - syncs[0][STEPS])
