"""Staging: frames dropped as stale over frames received, in the window."""


def read(run):
    got = run["close"]["consumed"] - run["open"]["consumed"]
    if got <= 0:
        return None
    return 100.0 * (run["close"]["dropped_stale"] - run["open"]["dropped_stale"]) / got
