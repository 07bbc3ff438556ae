"""Device: `memory_stats()["peak_bytes_in_use"]` of the fullest device."""


def read(run):
    if not run["peak_bytes"]:
        return None
    return run["peak_bytes"] / 1e9
