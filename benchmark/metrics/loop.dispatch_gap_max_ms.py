"""Learner loop: how long a publish keeps the loop from dispatching. The
program's `loop_dispatch_gap_max_s` is the longest interval between two
consecutive dispatches of the step in a metrics window, less what the loop
spent blocked in a metrics sync inside it; this is the largest over the
windows with a publish in flight: one submitted by the window's end
(`span_loop_publish_submit_n_total`) that was neither sent
(`span_publish_latency_n_total`), failed nor superseded at its start."""

GAP, SUBMITTED = "loop_dispatch_gap_max_s", "span_loop_publish_submit_n_total"
RESOLVED = ("span_publish_latency_n_total", "weights_publish_failed", "weights_coalesced")


def read(run):
    syncs = [s[2] for s in run["syncs"] if GAP in s[2] and SUBMITTED in s[2]]
    gaps = [b[GAP] for a, b in zip(syncs, syncs[1:])
            if b[SUBMITTED] > sum(a.get(k, 0) for k in RESOLVED)]
    if not gaps:
        return None
    return 1e3 * max(gaps)
