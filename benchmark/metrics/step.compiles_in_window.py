"""Device step: programs the process compiled or loaded inside the window
(the program's `compile_count_total`, last metrics window of the run's
window minus the first). Has to read 0: every shape is warm before the
window opens."""

KEY = "compile_count_total"


def read(run):
    counts = [s[2][KEY] for s in run["syncs"] if KEY in s[2]]
    if len(counts) < 2:
        return None
    return counts[-1] - counts[0]
