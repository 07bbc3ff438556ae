"""Learner loop: the window over its steps, host clock."""


def read(run):
    if run["steps"] <= 0:
        return None
    return 1e3 * run["window_s"] / run["steps"]
