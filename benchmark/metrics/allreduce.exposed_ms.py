"""Collectives: all-reduce time per step during which no compute runs on
that device, device trace (mean over the chips)."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["step_count"] or tr["allreduce_exposed_s"] is None:
        return None
    return 1e3 * tr["allreduce_exposed_s"] / tr["step_count"]
