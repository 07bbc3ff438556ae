"""Device step: busy time of the step program per step, device trace
(mean over the chips used)."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["step_count"]:
        return None
    return 1e3 * tr["step_busy_s"] / tr["step_count"]
